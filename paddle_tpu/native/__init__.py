"""Native (C++) runtime components, built on demand with g++ and loaded
via ctypes — the parts of the framework that stay host-native, mirroring
the reference's C++ runtime (data feed: framework/data_feed.cc)."""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIBS = {}


def _embed_flags(rpath: bool = False):
    """Compile/link flags for modules that embed CPython."""
    import sysconfig
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") \
        or sysconfig.get_config_var("VERSION")
    ld = [f"-L{libdir}"] if libdir else []
    if rpath and libdir:
        ld.append(f"-Wl,-rpath,{libdir}")
    return [f"-I{inc}"], ld + [f"-lpython{ver}"]


def _module_flags(name: str):
    """Extra compile/link flags per native module (capi embeds CPython)."""
    if name == "capi":
        # rpath so a standalone C program's dlopen finds libpython even
        # in a non-default prefix
        return _embed_flags(rpath=True)
    return [], []


def _compile(name: str, stem: str, suffix: str, cc, ldflags) -> str:
    """Build <name>.cpp into ``<stem>.<key><suffix>`` with
    ``cc <src> -o <out> ldflags``. ``key`` hashes the source's content
    and the command line — not its mtime: the artifacts are git-ignored
    and travel with copies of the tree, where a stale library from
    another checkout can be newer than the source it was not built
    from. Superseded builds of the same stem go."""
    src = os.path.join(_DIR, name + ".cpp")
    with open(src, "rb") as f:
        key = hashlib.sha256(
            f.read() + " ".join(cc + ldflags).encode()).hexdigest()[:16]
    out = os.path.join(_DIR, f"{stem}.{key}{suffix}")
    if not os.path.exists(out):
        tmp = f"{out}.tmp{os.getpid()}"
        subprocess.run(cc + [src, "-o", tmp] + ldflags, check=True,
                       capture_output=True, text=True)
        os.replace(tmp, out)  # atomic: a concurrent builder sees all or none
        for old in glob.glob(os.path.join(_DIR, f"{stem}.*{suffix}")):
            if old != out:
                os.remove(old)
    return out


def _build(name: str) -> str:
    cflags, ldflags = _module_flags(name)
    return _compile(name, "lib" + name, ".so",
                    ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                     "-pthread"] + cflags, ldflags)


class _BuildFailed:
    """Sentinel cached when a native build fails: attempt once per
    process, don't re-spawn a failing compiler on every call."""

    def __init__(self, err: Exception):
        self.err = err


def build_executable(name: str) -> str:
    """Build paddle_tpu/native/<name>.cpp as a standalone binary (the C++
    train demo — reference paddle/fluid/train/). Same once-per-process
    failure caching and locking as load()."""
    key = "exe:" + name
    with _LOCK:
        cached = _LIBS.get(key)
        if isinstance(cached, _BuildFailed):
            raise RuntimeError(
                f"native executable '{name}' previously failed to "
                f"build: {cached.err}") from cached.err
        if isinstance(cached, str):
            return cached
        cflags, ldflags = _embed_flags(rpath=True)
        try:
            exe = _compile(name, name, ".bin",
                           ["g++", "-O2", "-std=c++17", "-pthread"] + cflags,
                           ldflags)
        except Exception as e:
            _LIBS[key] = _BuildFailed(e)
            raise
        _LIBS[key] = exe
        return exe


def load(name: str) -> ctypes.CDLL:
    """Build (if stale) and dlopen paddle_tpu/native/<name>.cpp."""
    with _LOCK:
        lib = _LIBS.get(name)
        if isinstance(lib, _BuildFailed):
            raise RuntimeError(
                f"native module '{name}' previously failed to build: "
                f"{lib.err}") from lib.err
        if lib is None:
            try:
                lib = _LIBS[name] = ctypes.CDLL(_build(name))
            except Exception as e:
                _LIBS[name] = _BuildFailed(e)
                raise
        return lib


def datafeed_lib() -> ctypes.CDLL:
    lib = load("datafeed")
    if not getattr(lib, "_sigs_done", False):
        c = ctypes
        lib.df_create.restype = c.c_void_p
        lib.df_create.argtypes = [c.c_char_p]
        lib.df_set_filelist.argtypes = [c.c_void_p,
                                        c.POINTER(c.c_char_p), c.c_int]
        lib.df_set_batch.argtypes = [c.c_void_p, c.c_int]
        lib.df_set_threads.argtypes = [c.c_void_p, c.c_int]
        lib.df_load_into_memory.argtypes = [c.c_void_p]
        lib.df_local_shuffle.argtypes = [c.c_void_p, c.c_uint64]
        lib.df_epoch_begin.argtypes = [c.c_void_p]
        lib.df_next_batch.restype = c.c_int
        lib.df_next_batch.argtypes = [c.c_void_p]
        lib.df_slot_total.restype = c.c_int64
        lib.df_slot_total.argtypes = [c.c_void_p, c.c_int]
        lib.df_slot_copy.argtypes = [c.c_void_p, c.c_int, c.c_void_p,
                                     c.POINTER(c.c_int64)]
        lib.df_memory_size.restype = c.c_int64
        lib.df_memory_size.argtypes = [c.c_void_p]
        lib.df_release.argtypes = [c.c_void_p]
        lib._sigs_done = True
    return lib


def programdesc_lib() -> ctypes.CDLL:
    """Native ProgramDesc wire parser/validator (programdesc.cpp)."""
    lib = load("programdesc")
    if not getattr(lib, "_sigs_done", False):
        c = ctypes
        lib.pd_parse.restype = c.c_void_p
        lib.pd_parse.argtypes = [c.c_char_p, c.c_int64]
        lib.pd_ok.restype = c.c_int
        lib.pd_ok.argtypes = [c.c_void_p]
        lib.pd_json.restype = c.c_char_p
        lib.pd_json.argtypes = [c.c_void_p]
        lib.pd_release.argtypes = [c.c_void_p]
        lib._sigs_done = True
    return lib


def inspect_program_bytes(data: bytes) -> dict:
    """Parse+validate a serialized ProgramDesc natively; returns the JSON
    summary dict {n_blocks, n_ops, n_vars, ops: {type: count}, errors}."""
    import json
    lib = programdesc_lib()
    h = lib.pd_parse(data, len(data))
    try:
        # names in corrupt inputs can hold arbitrary bytes; the C++ side
        # hex-escapes them, replace is belt-and-braces
        return json.loads(lib.pd_json(h).decode("utf-8", "replace"))
    finally:
        lib.pd_release(h)
