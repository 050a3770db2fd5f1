"""Runtime core for paddle_tpu — the TPU-native equivalent of the reference's
pybind ``core`` extension module (reference: paddle/fluid/pybind/pybind.cc).

Where the reference exposes C++ Tensor/Scope/Executor objects backed by CUDA
allocations, this module backs the same API surface with ``jax.Array`` device
buffers managed by the XLA runtime: allocation, layout, and device transfer
are the compiler/runtime's job (reference memory/allocation/* is absorbed by
XLA — see SURVEY.md §2.1 "TPU mapping notes").

Contents:
  * VarDesc.VarType dtype enum (wire values match framework.proto:104).
  * Places: CPUPlace / TPUPlace (+ CUDAPlace compat alias → TPU).
  * LoDTensor / SelectedRows / LoDTensorArray runtime containers
    (reference: framework/lod_tensor.h:104, selected_rows.h:32).
  * Variable / Scope (reference: framework/variable.h:26, scope.h:46).
  * global flag registry (reference: platform/flags.cc ``FLAGS_*``).
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .proto import framework_pb2

__all__ = [
    "VarDesc", "CPUPlace", "TPUPlace", "CUDAPlace", "CUDAPinnedPlace",
    "Place", "LoDTensor", "Tensor", "SelectedRows", "LoDTensorArray",
    "LazyEmbeddingTable",
    "Variable", "Scope", "globals_", "get_flag", "set_flag",
    "dtype_to_np", "np_to_dtype", "dtype_to_jnp", "is_float_dtype",
    "is_compiled_with_tpu", "EOFException", "WorkerDeadError",
    "RpcProtocolError", "CheckpointError", "NumericFaultError",
    "StaleClusterViewError", "SpillCorruptionError",
]


class EOFException(Exception):
    """Raised by non-iterable DataLoader/PyReader ``next()`` when the
    underlying generator is drained (reference: the C++ reader's
    EnforceNotMet-EOF that ``exe.run`` surfaces in the py_reader loop;
    the user catches it, calls ``reader.reset()`` and starts the next
    epoch)."""


class WorkerDeadError(RuntimeError):
    """A collective operation (barrier / reduce) released because a
    participant was declared dead by the pserver's HeartBeatMonitor —
    survivors get this promptly (≈ the heartbeat timeout) instead of
    blocking for the full barrier deadline. The message names the dead
    worker id(s) so launchers can act (docs/FAULT_TOLERANCE.md)."""


class RpcProtocolError(ConnectionError):
    """The RPC wire framing is invalid — e.g. a length prefix beyond
    FLAGS_rpc_max_message_size (garbage or malicious peer). Never
    retried: retry applies to transient transport failures, not to a
    corrupted protocol stream."""


class CheckpointError(RuntimeError):
    """A checkpoint directory failed validation (missing manifest,
    missing files, size/CRC mismatches) or load_vars found missing
    files. The message aggregates EVERY bad file, not just the first."""


class SpillCorruptionError(CheckpointError):
    """A LazyEmbeddingTable spill-log segment (docs/PS_DATA_PLANE.md
    "Capacity tier") failed its CRC/size validation: the log was
    truncated, bit-flipped, or deleted out from under the table. The
    table REFUSES to serve the affected rows — same contract as a torn
    checkpoint (CheckpointError subclass, so existing rejection
    handlers keep working). Hot rows pinned in RAM keep serving."""


class StaleClusterViewError(RuntimeError):
    """A PS data RPC reached a server that no longer owns the shard —
    the pserver drained/handed its state off (or is a standby that has
    not been promoted), and the client's ClusterView is stale. Carries
    the server's current view as a plain dict in ``view_dict`` (None
    when the server itself has no newer view, e.g. an unpromoted
    standby); the RPC client installs it and replays the SAME encoded
    frame — same dedup token — against the new owner, so exactly-once
    application survives the re-route (docs/FAULT_TOLERANCE.md
    "Elastic membership")."""

    def __init__(self, msg: str, view=None):
        super().__init__(msg)
        self.view_dict = view


class NumericFaultError(FloatingPointError):
    """The numeric fault plane (FLAGS_check_nan_inf +
    FLAGS_nan_inf_action — docs/FAULT_TOLERANCE.md "Numeric faults")
    could not contain a NaN/Inf: rollback retries exhausted, no intact
    checkpoint to roll back to, or a tripped step the raise-mode
    localizer could not reproduce. Subclasses FloatingPointError so
    pre-existing FLAGS_check_nan_inf handlers keep catching it."""


class DeadlineExceededError(TimeoutError):
    """A request's propagated deadline expired before the work finished
    (docs/SERVING.md "Ingress & overload"): the serving ingress stamps
    each request with a budget, and queue wait, bucket dispatch, and PS
    row fetches (``ps_rpc.call_budget``) all check the remaining budget
    — an expired request surfaces this typed error (HTTP 504) instead
    of holding a worker or an RPC channel. Subclasses TimeoutError so
    pre-existing timeout handling keeps catching it. ``queue_wait_s``
    carries the time the request sat admitted-but-undispatched when the
    expiry happened in the queue."""

    def __init__(self, msg: str, queue_wait_s: float = None):
        super().__init__(msg)
        self.queue_wait_s = queue_wait_s


class OverloadedError(RuntimeError):
    """The serving admission plane shed this request (HTTP 429): the
    bounded admission queue is full, the token-bucket rate gate refused
    it, or the CoDel-style oldest-drop evicted it to keep accepted-
    request p99 bounded under sustained overload. ``retry_after_s`` is
    the server's drain-time estimate from its rolling QPS/latency stats
    — monotone in queue depth, so a well-behaved client backs off
    harder the deeper the overload (docs/SERVING.md)."""

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = float(retry_after_s)


class CircuitOpenError(ConnectionError):
    """A per-endpoint circuit breaker (fluid/ps_rpc.py, enabled by
    FLAGS_rpc_circuit_breaker) is OPEN for this pserver endpoint:
    recent calls died with transport/typed worker-dead errors, so new
    calls fail fast instead of burning their deadline against a dead
    server. Serving's sparse path catches it (with the other transport
    errors) and flips into serve-stale degraded mode; the breaker
    half-opens after FLAGS_rpc_breaker_reset_s and one probe call
    closes it again (docs/SERVING.md "Ingress & overload")."""


# --------------------------------------------------------------------------
# dtypes
# --------------------------------------------------------------------------
class _VarTypeEnum:
    """Mirror of framework.proto VarType.Type values (framework.proto:104)."""
    BOOL = framework_pb2.VarType.BOOL
    INT16 = framework_pb2.VarType.INT16
    INT32 = framework_pb2.VarType.INT32
    INT64 = framework_pb2.VarType.INT64
    FP16 = framework_pb2.VarType.FP16
    FP32 = framework_pb2.VarType.FP32
    FP64 = framework_pb2.VarType.FP64
    SIZE_T = framework_pb2.VarType.SIZE_T
    UINT8 = framework_pb2.VarType.UINT8
    INT8 = framework_pb2.VarType.INT8
    BF16 = framework_pb2.VarType.BF16

    LOD_TENSOR = framework_pb2.VarType.LOD_TENSOR
    SELECTED_ROWS = framework_pb2.VarType.SELECTED_ROWS
    FEED_MINIBATCH = framework_pb2.VarType.FEED_MINIBATCH
    FETCH_LIST = framework_pb2.VarType.FETCH_LIST
    STEP_SCOPES = framework_pb2.VarType.STEP_SCOPES
    LOD_RANK_TABLE = framework_pb2.VarType.LOD_RANK_TABLE
    LOD_TENSOR_ARRAY = framework_pb2.VarType.LOD_TENSOR_ARRAY
    PLACE_LIST = framework_pb2.VarType.PLACE_LIST
    READER = framework_pb2.VarType.READER
    RAW = framework_pb2.VarType.RAW
    TUPLE = framework_pb2.VarType.TUPLE


class VarDesc:
    VarType = _VarTypeEnum


_DTYPE_TO_NP = {
    _VarTypeEnum.BOOL: np.bool_,
    _VarTypeEnum.INT16: np.int16,
    _VarTypeEnum.INT32: np.int32,
    _VarTypeEnum.INT64: np.int64,
    _VarTypeEnum.FP16: np.float16,
    _VarTypeEnum.FP32: np.float32,
    _VarTypeEnum.FP64: np.float64,
    _VarTypeEnum.UINT8: np.uint8,
    _VarTypeEnum.INT8: np.int8,
}

_NP_TO_DTYPE = {np.dtype(v): k for k, v in _DTYPE_TO_NP.items()}
_NP_TO_DTYPE[np.dtype("bfloat16") if hasattr(np, "bfloat16") else jnp.bfloat16] = _VarTypeEnum.BF16

_STR_TO_DTYPE = {
    "bool": _VarTypeEnum.BOOL,
    "int16": _VarTypeEnum.INT16,
    "int32": _VarTypeEnum.INT32,
    "int64": _VarTypeEnum.INT64,
    "float16": _VarTypeEnum.FP16,
    "bfloat16": _VarTypeEnum.BF16,
    "float32": _VarTypeEnum.FP32,
    "float64": _VarTypeEnum.FP64,
    "uint8": _VarTypeEnum.UINT8,
    "int8": _VarTypeEnum.INT8,
}


def convert_np_dtype_to_dtype_(np_dtype) -> int:
    if isinstance(np_dtype, int):
        return np_dtype
    if isinstance(np_dtype, str):
        return _STR_TO_DTYPE[np_dtype]
    d = np.dtype(np_dtype) if not isinstance(np_dtype, np.dtype) else np_dtype
    if d in _NP_TO_DTYPE:
        return _NP_TO_DTYPE[d]
    if str(d) == "bfloat16":
        return _VarTypeEnum.BF16
    raise ValueError(f"unsupported numpy dtype {np_dtype}")


def np_to_dtype(np_dtype) -> int:
    return convert_np_dtype_to_dtype_(np_dtype)


def dtype_to_np(dtype: int):
    if dtype == _VarTypeEnum.BF16:
        return jnp.bfloat16
    return _DTYPE_TO_NP[dtype]


def dtype_to_jnp(dtype: int):
    """Device-side dtype. TPU-native narrowing: INT64→int32, FP64→float32
    (XLA on TPU has no fast 64-bit path; host serialization via dtype_to_np
    keeps the declared width)."""
    if dtype == _VarTypeEnum.BF16:
        return jnp.bfloat16
    if dtype == _VarTypeEnum.INT64:
        return jnp.int32
    if dtype == _VarTypeEnum.FP64:
        return jnp.float32
    return jnp.dtype(_DTYPE_TO_NP[dtype])


def is_float_dtype(dtype: int) -> bool:
    return dtype in (_VarTypeEnum.FP16, _VarTypeEnum.BF16, _VarTypeEnum.FP32,
                     _VarTypeEnum.FP64)


# --------------------------------------------------------------------------
# Places — device abstraction (reference: platform/place.h:26-79)
# --------------------------------------------------------------------------
class Place:
    """Base place."""
    def __eq__(self, other):
        return type(self) is type(other) and getattr(self, "_device_id", 0) == \
            getattr(other, "_device_id", 0)

    def __hash__(self):
        return hash((type(self).__name__, getattr(self, "_device_id", 0)))


class CPUPlace(Place):
    def __repr__(self):
        return "CPUPlace"

    def jax_device(self):
        # local_devices, not devices: in multi-process mode the global
        # list starts with process 0's devices — placing host data there
        # from another rank would create a non-addressable array
        try:
            return jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            return jax.local_devices()[0]


class TPUPlace(Place):
    """The accelerator place: local device ``device_id`` of JAX's default
    backend. Under JAX_PLATFORMS=cpu (the test mode) that backend is the
    CPU, so programs written against TPUPlace run in the suite; which
    backend it is, is JAX's choice at start-up and never a fallback made
    here."""
    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def __repr__(self):
        return f"TPUPlace({self._device_id})"

    def get_device_id(self):
        return self._device_id

    def jax_device(self):
        devs = jax.local_devices()
        if not 0 <= self._device_id < len(devs):
            raise ValueError(
                f"{self!r}: this process has {len(devs)} local "
                f"{devs[0].platform} device(s)")
        return devs[self._device_id]


# Compatibility alias: reference scripts say CUDAPlace; on this framework that
# means "the accelerator", i.e. the TPU chip of that ordinal.
CUDAPlace = TPUPlace


class CUDAPinnedPlace(CPUPlace):
    def __repr__(self):
        return "CUDAPinnedPlace"


def is_compiled_with_tpu() -> bool:
    """Whether JAX's default backend is a TPU. A backend that fails to
    initialize raises from here: a program meant for the chip must not
    land on the CPU because the chip could not be reached."""
    return jax.devices()[0].platform == "tpu"


def is_compiled_with_cuda() -> bool:
    # CUDA never exists here; scripts gating on this will take the CPU path,
    # so report accelerator presence instead for behavioural parity.
    return is_compiled_with_tpu()


def start_forked_quietly(procs):
    """Start fork-context worker processes with the fork-under-threads
    warnings suppressed: fork is deliberate at these call sites (reader
    closures can't be pickled for spawn) and the children never touch
    JAX, so an inherited JAX-internal lock can't deadlock them."""
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        warnings.simplefilter("ignore", RuntimeWarning)
        for p in procs:
            p.start()


def _as_place(place) -> Place:
    if place is None:
        return TPUPlace(0) if is_compiled_with_tpu() else CPUPlace()
    return place


# --------------------------------------------------------------------------
# Tensors
# --------------------------------------------------------------------------
def _to_device_array(data, place: Optional[Place] = None, dtype=None):
    if isinstance(data, jax.Array) and dtype is None:
        return data
    arr = np.asarray(data, dtype=dtype)
    # Device integer policy: 32-bit. TPU has no native int64 ALU path and
    # jax runs x64-off, so 64-bit feeds are cast explicitly here (instead
    # of leaking a per-call truncation warning from jax); the executor's
    # fetch boundary restores the program-declared int64 dtype, so user
    # code still sees the reference's int64 contracts (e.g. sequence_pad
    # Length — reference sequence_pad_op.cc).
    if not jax.config.jax_enable_x64 and arr.dtype in (np.int64, np.uint64):
        tgt = np.int32 if arr.dtype == np.int64 else np.uint32
        info = np.iinfo(tgt)
        if arr.size and (int(arr.min()) < info.min
                         or int(arr.max()) > info.max):
            # astype would WRAP (e.g. a 64-bit hashed CTR feature id
            # becoming a negative row index) — that corruption is silent
            # and unrecoverable at the fetch boundary, so refuse. Feeds
            # carrying genuine 64-bit ids belong on the host-side PS
            # lookup path (distributed_lookup_table), not on-device.
            raise ValueError(
                f"int64/uint64 feed value out of {np.dtype(tgt).name} "
                f"range (min={arr.min()}, max={arr.max()}): the device "
                "integer width is 32-bit (TPU has no native int64 path). "
                "Route >32-bit ids through the parameter-server lookup "
                "(distributed_lookup_table) or pre-hash them below 2^31.")
        arr = arr.astype(tgt)
    if place is None:
        return jnp.asarray(arr)
    return jax.device_put(arr, _as_place(place).jax_device())


class LoDTensor:
    """Dense tensor + level-of-detail offsets for ragged sequence batches
    (reference: framework/lod_tensor.h:104). The buffer is a jax.Array; LoD is
    host-side metadata (TPU kernels consume padded/packed forms, the LoD
    records the ragged structure)."""

    __slots__ = ("_array", "_lod")

    def __init__(self, array=None, lod: Optional[List[List[int]]] = None):
        self._array = array
        self._lod = [list(l) for l in lod] if lod else []

    # -- reference API surface -------------------------------------------
    def set(self, np_array, place=None):
        self._array = _to_device_array(np_array, place)

    def set_lod(self, lod):
        self._lod = [list(l) for l in lod]

    def lod(self):
        return [list(l) for l in self._lod]

    def set_recursive_sequence_lengths(self, seq_lens):
        # lengths [[2,3]] -> offsets [[0,2,5]]
        lod = []
        for lens in seq_lens:
            offs = [0]
            for ln in lens:
                offs.append(offs[-1] + int(ln))
            lod.append(offs)
        self._lod = lod

    def recursive_sequence_lengths(self):
        out = []
        for offs in self._lod:
            out.append([offs[i + 1] - offs[i] for i in range(len(offs) - 1)])
        return out

    def has_valid_recursive_sequence_lengths(self):
        if not self._lod:
            return True
        n = self._array.shape[0] if self._array is not None else 0
        return self._lod[-1][-1] == n

    def shape(self):
        return list(self._array.shape) if self._array is not None else []

    def _dtype(self):
        return self._array.dtype if self._array is not None else None

    def __array__(self, dtype=None):
        a = np.asarray(self._array)
        return a.astype(dtype) if dtype is not None else a

    def numpy(self):
        return np.asarray(self._array)

    @property
    def array(self):
        return self._array

    def __len__(self):
        return int(self._array.shape[0]) if self._array is not None else 0

    def __repr__(self):
        return f"LoDTensor(shape={self.shape()}, lod={self._lod})"


Tensor = LoDTensor


class SelectedRows:
    """Sparse row-set tensor: a value tensor whose i-th row corresponds to
    logical row ``rows[i]`` of a [height, ...] dense tensor (reference:
    framework/selected_rows.h:32). Used for embedding gradients and the
    sparse parameter-server path."""

    __slots__ = ("_rows", "_height", "_value")

    def __init__(self, rows=None, height: int = 0):
        self._rows = list(rows) if rows is not None else []
        self._height = int(height)
        self._value = LoDTensor()

    def rows(self):
        return self._rows

    def set_rows(self, rows):
        self._rows = [int(r) for r in rows]

    def height(self):
        return self._height

    def set_height(self, h):
        self._height = int(h)

    def get_tensor(self) -> LoDTensor:
        return self._value

    def sync_index(self):
        pass

    def to_dense(self) -> jnp.ndarray:
        val = self._value.array
        dense = jnp.zeros((self._height,) + tuple(val.shape[1:]), val.dtype)
        return dense.at[jnp.asarray(self._rows, jnp.int32)].add(val)

    def __repr__(self):
        return f"SelectedRows(height={self._height}, nrows={len(self._rows)})"


class LoDTensorArray(list):
    """reference: framework/lod_tensor_array.h — a std::vector<LoDTensor>."""
    pass


class _SpillTier:
    """Tier state of one LazyEmbeddingTable (docs/PS_DATA_PLANE.md
    "Capacity tier"): the spill store + cold-row map, the entry-gate
    counters, decay-shrink scores, and the telemetry counters the
    pserver stats plane scrapes. ``store`` is None for an entry-gated
    but un-spilled table."""

    __slots__ = ("store", "spill_path", "hot_rows", "quant", "seg_rows",
                 "entry_threshold", "track_scores", "cold", "backing",
                 "seg_live", "seg_cold", "freq", "scores",
                 "hits", "misses", "promoted_rows", "spilled_rows_total",
                 "clean_evictions", "spill_batches", "entry_denied",
                 "grad_dropped_rows", "poison_dropped_rows",
                 "shrunk_rows", "shrink_runs")

    def __init__(self, spill_path, hot_rows, quant, seg_rows,
                 entry_threshold, dim, dtype, track_scores=None):
        self.spill_path = spill_path
        self.hot_rows = int(hot_rows)
        self.quant = quant
        self.seg_rows = int(seg_rows)
        self.entry_threshold = int(entry_threshold)
        # per-row touch scores feed shrink(); tracked when the entry
        # gate is on (or explicitly requested) — a plain spill tier
        # skips the per-touch dict update on its hot path
        self.track_scores = bool(entry_threshold > 0
                                 if track_scores is None
                                 else track_scores)
        self.store = None
        if spill_path:
            from . import slab_spill
            self.store = slab_spill.SpillStore(spill_path, dim, dtype)
        self.cold: Dict[int, tuple] = {}      # id -> (seg_id, row_pos)
        # CLEAN promoted rows keep their disk copy as backing: evicting
        # an unmodified row just flips it back to cold — zero write-back
        # (page-cache dirty-bit semantics). apply_grad dirties.
        self.backing: Dict[int, tuple] = {}   # hot id -> (seg, pos)
        self.seg_live: Dict[int, int] = {}    # seg -> cold+backing refs
        # seg -> COLD refs only, maintained incrementally wherever cold
        # refs move — tier_stats() reads it so a telemetry scrape under
        # the grad lock is O(segments), never O(spilled rows)
        self.seg_cold: Dict[int, int] = {}
        self.freq: Dict[int, int] = {}        # unentered id -> pull count
        self.scores: Dict[int, float] = {}    # materialized id -> score
        self.hits = 0
        self.misses = 0
        self.promoted_rows = 0
        self.spilled_rows_total = 0
        self.clean_evictions = 0
        self.spill_batches = 0
        self.entry_denied = 0
        self.grad_dropped_rows = 0
        self.poison_dropped_rows = 0
        self.shrunk_rows = 0
        self.shrink_runs = 0

    def deref_seg(self, sid) -> None:
        self.seg_live[sid] -= 1
        if self.seg_live[sid] == 0:
            self.seg_live.pop(sid)
            self.store.free(sid)


class LazyEmbeddingTable:
    """Beyond-HBM host-RAM embedding table for the sparse PS path
    (reference: framework/fleet/fleet_wrapper.h:86-190 — DownpourSparseTable
    pull creates features on first touch; memory is bounded by feature
    count, not by the logical [height, dim] shape, and features can be
    evicted/shrunk).

    Rows materialize on first access with a deterministic per-row init, so
    a 1e9-parameter logical table costs only O(touched rows) memory; an
    optional LRU bound evicts least-recently-used rows (an evicted, later
    re-touched row re-initializes — the reference's shrink() makes the
    same trade).

    Storage is a CONTIGUOUS slab (``_data``) plus an id→slot index, so
    the PS-plane hot paths are vectorized: ``get_rows`` is one
    fancy-index gather and ``apply_grad`` one ``np.subtract.at`` scatter
    — per-id python work is a single dict lookup, not a per-row
    stack/astype (the pserver applies thousands of rows per step on the
    wide_deep lanes; docs/PS_DATA_PLANE.md).

    CAPACITY TIER (docs/PS_DATA_PLANE.md "Capacity tier"): with
    ``spill_path`` + ``hot_rows`` the slab becomes the PINNED HOT SET of
    a two-tier table — LRU overflow writes back to an mmap-backed,
    CRC-stamped segment log (``fluid/slab_spill.SpillStore``), cold
    rows promote back into the slab on touch (one segment read per
    touched segment, not one seek per id), and ``at_rest_quant``
    ("fp16"/"int8") stores spilled rows through the PR 11 wire codec at
    2-3.8× density with dequant-on-touch feeding the
    FLAGS_ps_reject_nonfinite guard. ``entry_threshold`` > 1
    frequency-gates entry creation (an id must be PULLED that many
    times before it earns a slot — reference PSLib entry gating) and
    ``shrink()`` decays per-row touch scores and drops idle rows. All
    of it opt-in: an unconfigured table runs the exact pre-tier code
    paths."""

    __slots__ = ("height", "dim", "dtype", "seed", "scale", "max_rows",
                 "_index", "_data", "_free", "evictions", "_tier")

    def __init__(self, height: int, dim: int, seed: int = 0,
                 scale: Optional[float] = None, max_rows: Optional[int] = None,
                 dtype=np.float32, spill_path: Optional[str] = None,
                 hot_rows: Optional[int] = None, at_rest_quant: str = "",
                 entry_threshold: int = 0, spill_seg_rows: int = 0,
                 track_scores: Optional[bool] = None):
        from collections import OrderedDict
        self.height = int(height)
        self.dim = int(dim)
        self.dtype = np.dtype(dtype)
        self.seed = int(seed)
        self.scale = float(scale) if scale is not None \
            else 1.0 / float(np.sqrt(dim))
        self.max_rows = int(max_rows) if max_rows else None
        # id -> slot in _data; insertion order doubles as LRU order when
        # max_rows bounds the table (and as the hot set's promotion/
        # eviction order when the spill tier bounds it)
        self._index: "OrderedDict[int, int]" = OrderedDict()
        self._data = np.empty((0, self.dim), self.dtype)
        self._free: list = []  # recycled slots of evicted rows
        self.evictions = 0
        self._tier = None
        tiered = bool(spill_path) and bool(hot_rows)
        if self.max_rows is not None and (
                tiered or int(entry_threshold) > 0 or track_scores):
            # the tiered code paths never run the max_rows eviction, so
            # accepting both would SILENTLY drop the RAM bound
            raise ValueError(
                "LazyEmbeddingTable: max_rows (evict-to-oblivion LRU) "
                "cannot combine with the capacity tier (spill/"
                "entry_threshold/track_scores) — the tier's hot_rows "
                "IS the RAM bound there")
        if at_rest_quant not in ("", "fp16", "int8"):
            raise ValueError(
                f"at_rest_quant={at_rest_quant!r} — expected '' | "
                f"'fp16' | 'int8'")
        if tiered or int(entry_threshold) > 0 or track_scores:
            self._tier = _SpillTier(
                spill_path=spill_path if tiered else None,
                hot_rows=int(hot_rows) if tiered else 0,
                quant=at_rest_quant,
                seg_rows=int(spill_seg_rows) or 4096,
                entry_threshold=int(entry_threshold),
                dim=self.dim, dtype=self.dtype,
                track_scores=track_scores)

    def _init_row(self, r: int) -> np.ndarray:
        rs = np.random.RandomState((self.seed * 1000003 + int(r))
                                   % (2 ** 31 - 1))
        return rs.uniform(-self.scale, self.scale,
                          self.dim).astype(self.dtype)

    def _grow_to(self, min_cap: int) -> None:
        """Grow the slab to at least ``min_cap`` rows by doubling —
        the ONE growth policy every claim/install path shares."""
        if min_cap <= len(self._data):
            return
        cap = max(1024, 2 * len(self._data), min_cap)
        grown = np.empty((cap, self.dim), self.dtype)
        grown[:len(self._data)] = self._data
        self._data = grown

    def _claim_slot(self) -> int:
        """Claim a slab slot (recycled or new, growing by doubling).
        The caller must insert the slot into ``_index`` before the next
        claim — fresh-slot numbering assumes every prior slot is either
        indexed or free (use ``_claim_slots`` for bulk claims)."""
        n_alloc = len(self._index) + len(self._free)
        s = self._free.pop() if self._free else n_alloc
        self._grow_to(s + 1)
        return s

    def _claim_slots(self, n: int) -> np.ndarray:
        """Claim ``n`` slots at once (recycled first, then a contiguous
        fresh run) WITHOUT requiring interleaved index insertions."""
        free = self._free
        out = [free.pop() for _ in range(min(n, len(free)))]
        m = n - len(out)
        if m:
            base = len(self._index) + len(free) + len(out)
            self._grow_to(base + m)
            out.extend(range(base, base + m))
        return np.asarray(out, np.int64)

    def _alloc(self, r: int) -> int:
        """Materialize row ``r``: claim a slot (recycled or new, growing
        the slab by doubling), init deterministically, LRU-evict."""
        s = self._claim_slot()
        self._data[s] = self._init_row(r)
        self._index[r] = s
        if self.max_rows is not None and len(self._index) > self.max_rows:
            _evicted, old_slot = self._index.popitem(last=False)  # LRU out
            self._free.append(old_slot)
            self.evictions += 1
        return s

    def _slots_of(self, ids: np.ndarray) -> list:
        """Slot per id, materializing misses (UNBOUNDED tables only —
        slots stay valid for the whole batch because nothing evicts).
        One dict hit per id."""
        get = self._index.get
        alloc = self._alloc
        return [s if (s := get(r)) is not None else alloc(r)
                for r in ids.tolist()]

    def _slot_of_bounded(self, r: int) -> int:
        s = self._index.get(r)
        if s is None:
            return self._alloc(r)
        self._index.move_to_end(r)
        return s

    def get_rows(self, ids) -> np.ndarray:
        ids = np.asarray(ids).reshape(-1)
        if not len(ids):
            return np.zeros((0, self.dim), self.dtype)
        if self._tier is not None:
            return self._get_rows_tiered(ids)
        if self.max_rows is None:
            slots = self._slots_of(ids)  # FIRST: may grow/replace _data
            return self._data[slots]
        # bounded table: an eviction later in THIS batch may recycle an
        # earlier id's slot — copy each row at touch time (the dict
        # implementation's semantics) instead of batch-gathering stale
        # slot numbers
        out = np.empty((len(ids), self.dim), self.dtype)
        for i, r in enumerate(ids.tolist()):
            s = self._slot_of_bounded(r)  # FIRST: may grow/replace _data
            out[i] = self._data[s]
        return out

    def apply_grad(self, ids, grads, lr: float) -> None:
        """Row-wise SGD: rows[id] -= lr * grad (duplicate ids accumulate,
        in id order — one vectorized scatter for unbounded tables)."""
        ids = np.asarray(ids).reshape(-1)
        if not len(ids):
            return
        grads = np.asarray(grads).reshape(len(ids), self.dim)
        step = (lr * grads).astype(self.dtype, copy=False)
        if self._tier is not None:
            self._apply_grad_tiered(ids, step)
            return
        if self.max_rows is None:
            slots = np.asarray(self._slots_of(ids), np.int64)
            np.subtract.at(self._data, slots, step)
            return
        # bounded: apply at touch time so a later in-batch eviction
        # can't scatter into a recycled slot
        for i, r in enumerate(ids.tolist()):
            s = self._slot_of_bounded(r)  # FIRST: may grow/replace _data
            self._data[s] -= step[i]

    # -- capacity tier (docs/PS_DATA_PLANE.md "Capacity tier") -------------
    def _promote_for(self, id_list, t) -> None:
        """Promote every cold id in ``id_list`` into the hot slab with
        ONE store read per touched segment (the batched I/O fan-in —
        never one seek per id). Counts hot hits / cold misses."""
        idx = self._index
        if t.store is None:
            t.hits += sum(1 for r in id_list if r in idx)
            return
        cold = t.cold
        by_seg: Dict[int, list] = {}
        queued = set()
        for r in id_list:
            if r in idx or r in queued:
                t.hits += 1
                continue
            cr = cold.get(r)
            if cr is not None:
                by_seg.setdefault(cr[0], []).append(r)
                queued.add(r)
        for sid in sorted(by_seg):
            self._promote_segment(sid, by_seg[sid], t)

    def _promote_segment(self, sid, rs, t) -> None:
        seg_ids, rows = t.store.read(sid)  # CRC-verified, dequantized
        n = len(rs)
        t.misses += n
        cold = t.cold
        pos = np.fromiter((cold[r][1] for r in rs), np.int64, n)
        rs_arr = np.asarray(rs, np.int64)
        if (seg_ids[pos] != rs_arr).any():
            bad = int(np.argmax(seg_ids[pos] != rs_arr))
            raise SpillCorruptionError(
                f"spill segment {sid}: row {int(pos[bad])} holds id "
                f"{int(seg_ids[pos[bad]])}, cold map expected "
                f"{rs[bad]} — log/directory desynchronized")
        take = rows[pos]
        # dequant-on-touch guard: a poisoned spilled row surfaces HERE,
        # exactly like a poisoned wire frame surfaces at decode
        # (FLAGS_ps_reject_nonfinite — docs/FAULT_TOLERANCE.md)
        mode = str(globals_["FLAGS_ps_reject_nonfinite"] or "") \
            if np.issubdtype(self.dtype, np.floating) else ""
        dropped = set()
        if mode:
            finite = np.isfinite(take).all(axis=1)
            if not finite.all():
                if mode == "reject":
                    bad = rs[int(np.argmin(finite))]
                    raise NumericFaultError(
                        f"spilled embedding row {bad} dequantized "
                        f"non-finite at touch "
                        f"(FLAGS_ps_reject_nonfinite=reject) — "
                        f"refusing to serve it")
                # drop: poisoned rows re-initialize deterministically
                # (the disk copy is poison — no clean backing for them)
                for i in np.flatnonzero(~finite):
                    take[i] = self._init_row(rs[int(i)])
                    dropped.add(rs[int(i)])
                    t.poison_dropped_rows += 1
        # bulk install: one fancy-index copy + one dict batch-update
        # (the promote loop is the cold-pull hot path — per-row python
        # here caps the spilled lane's throughput)
        slots = self._claim_slots(n)
        self._data[slots] = take
        self._index.update(zip((int(r) for r in rs), slots.tolist()))
        # NO score bump here: the caller's gather loop finds the id hot
        # now and bumps exactly once — a cold touch must not outscore a
        # hot touch
        # a CLEAN promote keeps its disk copy as backing — the segment
        # ref just moves cold→backing, and a later eviction of the
        # still-unmodified row is free (no re-encode, no write)
        backing = t.backing
        for r in rs:
            entry = cold.pop(r)
            if r in dropped:
                t.deref_seg(entry[0])
            else:
                backing[r] = entry
        t.seg_cold[sid] -= n
        if t.seg_cold[sid] <= 0:
            t.seg_cold.pop(sid)
        t.promoted_rows += n

    def _alloc_tiered(self, r: int) -> int:
        s = self._claim_slot()
        self._data[s] = self._init_row(r)
        self._index[r] = s
        t = self._tier
        if t.track_scores:
            t.scores[r] = t.scores.get(r, 0.0) + 1.0
        return s

    def _spill_overflow(self) -> None:
        """Write back the LRU overflow of the hot set as spill-log
        segments (batch-level granularity: eviction runs once per
        get_rows/apply_grad call, AFTER the whole batch touched, so an
        id can never lose its slot to a sibling id of the same batch
        mid-gather)."""
        t = self._tier
        if t.store is None:
            return
        n_over = len(self._index) - t.hot_rows
        if n_over <= 0:
            return
        backing, cold, free = t.backing, t.cold, self._free
        dirty_ids: list = []
        dirty_slots: list = []
        for _ in range(n_over):
            r, s = self._index.popitem(last=False)  # LRU out
            free.append(s)
            b = backing.pop(r, None)
            if b is not None:
                # CLEAN eviction: the disk copy is still the row's
                # value — flip back to cold, zero bytes written
                cold[r] = b
                t.seg_cold[b[0]] = t.seg_cold.get(b[0], 0) + 1
                t.clean_evictions += 1
            else:
                dirty_ids.append(r)
                dirty_slots.append(s)
        if dirty_ids:
            # slots were freed above but nothing claims between here
            # and the gather — the rows are intact
            rows = self._data[np.asarray(dirty_slots, np.int64)]
            ids_arr = np.asarray(dirty_ids, np.int64)
            for lo in range(0, len(dirty_ids), t.seg_rows):
                hi = min(lo + t.seg_rows, len(dirty_ids))
                sid = t.store.append(ids_arr[lo:hi], rows[lo:hi],
                                     quant=t.quant)
                t.seg_live[sid] = hi - lo
                t.seg_cold[sid] = hi - lo
                for j in range(lo, hi):
                    cold[int(ids_arr[j])] = (sid, j - lo)
                t.spill_batches += 1
        t.spilled_rows_total += n_over

    def _get_rows_tiered(self, ids: np.ndarray) -> np.ndarray:
        t = self._tier
        id_list = [int(r) for r in ids.tolist()]
        self._promote_for(id_list, t)
        idx = self._index
        thr = t.entry_threshold
        track = t.track_scores
        slots = np.empty(len(id_list), np.int64)
        gated: Dict[int, int] = {}  # out position -> id (no slot yet)
        for i, r in enumerate(id_list):
            s = idx.get(r)
            if s is None:
                if thr > 1:
                    c = t.freq.get(r, 0) + 1
                    if c < thr:
                        # below the entry gate: serve the deterministic
                        # init row WITHOUT materializing — a garbage id
                        # never earns a slot (reference PSLib entry
                        # frequency gating)
                        t.freq[r] = c
                        t.entry_denied += 1
                        gated[i] = r
                        slots[i] = -1
                        continue
                    t.freq.pop(r, None)
                s = self._alloc_tiered(r)
            else:
                idx.move_to_end(r)
                if track:
                    t.scores[r] = t.scores.get(r, 0.0) + 1.0
            slots[i] = s
        out = np.empty((len(id_list), self.dim), self.dtype)
        live = slots >= 0
        if live.all():
            out[:] = self._data[slots]
        elif live.any():
            out[live] = self._data[slots[live]]
        for i, r in gated.items():
            out[i] = self._init_row(r)
        self._spill_overflow()
        return out

    def _apply_grad_tiered(self, ids: np.ndarray, step: np.ndarray) -> None:
        t = self._tier
        id_list = [int(r) for r in ids.tolist()]
        self._promote_for(id_list, t)
        idx = self._index
        thr = t.entry_threshold
        track = t.track_scores
        backing = t.backing
        slots = np.empty(len(id_list), np.int64)
        keep = np.ones(len(id_list), bool)
        for i, r in enumerate(id_list):
            s = idx.get(r)
            if s is None:
                if thr > 1:
                    # entry creation is PULL-driven (reference PSLib):
                    # a grad for an id that never earned a slot is
                    # dropped, counted — garbage ids can't train
                    keep[i] = False
                    t.grad_dropped_rows += 1
                    continue
                s = self._alloc_tiered(r)
            else:
                idx.move_to_end(r)
                if track:
                    t.scores[r] = t.scores.get(r, 0.0) + 1.0
            # the update DIRTIES the row: its clean disk copy (if any)
            # is no longer its value — drop the backing ref
            if backing:
                b = backing.pop(r, None)
                if b is not None:
                    t.deref_seg(b[0])
            slots[i] = s
        if keep.all():
            np.subtract.at(self._data, slots, step)
        elif keep.any():
            np.subtract.at(self._data, slots[keep], step[keep])
        self._spill_overflow()

    def shrink(self, decay: float = 0.5, threshold: float = 0.5) -> int:
        """Decay-based shrink (reference PSLib table shrink / entry
        expiry): every materialized row's touch score multiplies by
        ``decay``; rows falling below ``threshold`` are DROPPED — hot
        slots freed, cold rows erased from the spill log's live set
        (fully-dead segments freed and eventually compacted away) — and
        so are below-threshold entry-gate counters. A dropped id that
        comes back re-initializes deterministically, the same trade the
        in-RAM LRU bound makes. Returns the number of rows dropped."""
        t = self._tier
        if t is None or not t.track_scores:
            raise RuntimeError(
                "shrink() needs touch-score tracking — construct the "
                "table with entry_threshold > 0 or track_scores=True "
                "(FLAGS_ps_entry_threshold / FLAGS_ps_slab_track_scores "
                "on a pserver)")
        decay = float(decay)
        dropped = 0
        new_scores: Dict[int, float] = {}
        for r, sc in t.scores.items():
            sc *= decay
            if sc >= threshold:
                new_scores[r] = sc
                continue
            s = self._index.pop(r, None)
            if s is not None:
                self._free.append(s)
                b = t.backing.pop(r, None)
                if b is not None:
                    t.deref_seg(b[0])
                dropped += 1
                continue
            cr = t.cold.pop(r, None)
            if cr is not None:
                t.seg_cold[cr[0]] -= 1
                if t.seg_cold[cr[0]] <= 0:
                    t.seg_cold.pop(cr[0])
                t.deref_seg(cr[0])
                dropped += 1
        t.scores = new_scores
        if t.freq:
            t.freq = {r: c for r, c in
                      ((r, int(c * decay)) for r, c in t.freq.items())
                      if c > 0}
        t.shrunk_rows += dropped
        t.shrink_runs += 1
        return dropped

    def tier_stats(self) -> Dict[str, Any]:
        """Telemetry gauges of the capacity tier (scraped through the
        pserver stats plane as ``ps_server_slab_*`` — docs/
        OBSERVABILITY.md). Empty dict for an untiered table."""
        t = self._tier
        if t is None:
            return {}
        cold_rows = len(t.cold)
        spilled_bytes = 0
        if t.store is not None and cold_rows:
            # bytes attributable to the COLD rows (backing copies of
            # clean hot rows are a write-elision byproduct, not spilled
            # capacity): the incrementally-maintained per-segment cold
            # counts keep this O(segments) — a stats scrape under the
            # grad lock must never walk every spilled row
            for sid, n_cold in t.seg_cold.items():
                sm = t.store.seg_meta(sid)
                if sm["n_rows"]:
                    spilled_bytes += int(
                        round(sm["row_bytes"] * n_cold / sm["n_rows"]))
        logical = cold_rows * self.dim * self.dtype.itemsize
        touches = t.hits + t.misses
        out = {
            "resident_rows": len(self._index),
            "spilled_rows": cold_rows,
            "resident_bytes": len(self._index) * self.dim
            * self.dtype.itemsize,
            "spilled_bytes": spilled_bytes,
            "logical_spilled_bytes": logical,
            "density_x": round(logical / spilled_bytes, 3)
            if spilled_bytes else 0.0,
            "hits": t.hits, "misses": t.misses,
            "hit_rate": round(t.hits / touches, 4) if touches else 0.0,
            "backing_rows": len(t.backing),
            "promoted_rows": t.promoted_rows,
            "spilled_rows_total": t.spilled_rows_total,
            "clean_evictions": t.clean_evictions,
            "spill_batches": t.spill_batches,
            "entry_denied": t.entry_denied,
            "grad_dropped_rows": t.grad_dropped_rows,
            "poison_dropped_rows": t.poison_dropped_rows,
            "shrunk_rows": t.shrunk_rows,
            "shrink_runs": t.shrink_runs,
            "gate_pending_ids": len(t.freq),
        }
        if t.store is not None:
            out.update({
                "spill_file_bytes": t.store.file_bytes(),
                "spill_live_bytes": t.store.live_bytes(),
                "store_reads": t.store.reads,
                "store_writes": t.store.writes,
                "compactions": t.store.compactions,
                "crc_failures": t.store.crc_failures,
            })
        return out

    def close_spill(self, unlink: bool = False) -> None:
        t = self._tier
        if t is not None and t.store is not None:
            (t.store.unlink if unlink else t.store.close)()

    # -- section-stream plumbing (slab_spill.table_sections /
    #    build_table_from_sections — the handoff + checkpoint legs) ------
    def export_meta(self) -> Dict[str, Any]:
        meta = {"height": self.height, "dim": self.dim,
                "seed": self.seed, "scale": self.scale,
                "max_rows": self.max_rows, "dtype": self.dtype.str,
                "evictions": self.evictions}
        t = self._tier
        if t is not None:
            meta["tier"] = {"hot_rows": t.hot_rows, "quant": t.quant,
                            "entry_threshold": t.entry_threshold,
                            "seg_rows": t.seg_rows,
                            "track_scores": t.track_scores,
                            "spilled": t.store is not None}
        return meta

    def _install_hot_rows(self, ids: np.ndarray, rows: np.ndarray) -> None:
        """Streaming rebuild: append one hot chunk to the slab in
        order (fresh table only — slots contiguous from 0)."""
        base = len(self._index)
        need = base + len(rows)
        # doubling growth (shared policy): per-chunk exact sizing would
        # re-copy the whole accumulated slab once per streamed chunk
        self._grow_to(need)
        self._data[base:need] = np.asarray(rows, self.dtype)
        for i, r in enumerate(ids.tolist()):
            self._index[int(r)] = base + i

    def _install_spilled_segment(self, record, sm) -> None:
        """Streaming rebuild: install one VERBATIM spill record plus
        its live map (bit-identical residency on the destination)."""
        t = self._tier
        if t is None or t.store is None:
            raise SpillCorruptionError(
                "slab stream carries spilled segments but the rebuilt "
                "table has no spill tier")
        sid = t.store.append_raw(record, int(sm["n_rows"]),
                                 sm.get("quant", ""),
                                 int(sm["row_bytes"]),
                                 expect_crc=sm.get("crc"))
        n_rows = int(sm["n_rows"])
        ids = np.frombuffer(record[:n_rows * 8], np.int64) \
            if not isinstance(record, np.ndarray) \
            else np.frombuffer(record.tobytes()[:n_rows * 8], np.int64)
        runs = sm.get("live_runs")
        if runs is None:
            live = sm.get("live_pos")
            live = range(n_rows) if live is None else live
        else:
            live = (p for start, n in runs
                    for p in range(int(start), int(start) + int(n)))
        n_live = 0
        for pos in live:
            t.cold[int(ids[int(pos)])] = (sid, int(pos))
            n_live += 1
        t.seg_live[sid] = n_live
        if n_live:
            t.seg_cold[sid] = n_live
        else:
            t.seg_live.pop(sid)
            t.store.free(sid)

    def _export_gate_state(self):
        t = self._tier
        empty = np.empty(0, np.int64)
        if t is None:
            return empty, np.empty(0, np.float32), empty, empty
        sc_ids = np.fromiter(t.scores.keys(), np.int64, len(t.scores))
        sc_vals = np.fromiter(t.scores.values(), np.float32,
                              len(t.scores))
        fq_ids = np.fromiter(t.freq.keys(), np.int64, len(t.freq))
        fq_cnt = np.fromiter(t.freq.values(), np.int64, len(t.freq))
        return sc_ids, sc_vals, fq_ids, fq_cnt

    def _import_gate_state(self, sc_ids, sc_vals, fq_ids, fq_cnt) -> None:
        t = self._tier
        if t is None:
            return
        t.scores = {int(r): float(v)
                    for r, v in zip(sc_ids.tolist(), sc_vals.tolist())}
        t.freq = {int(r): int(c)
                  for r, c in zip(fq_ids.tolist(), fq_cnt.tolist())}

    # -- handoff (elastic membership, docs/FAULT_TOLERANCE.md) ------------
    def export_state(self):
        """Snapshot for a CRC-manifested shard handoff: (meta, ids,
        rows). ``ids`` lists materialized row ids in LRU order (oldest
        first — OrderedDict insertion order IS the eviction order) and
        ``rows`` their current values, so ``import_state`` on the
        destination rebuilds a bit-identical table INCLUDING future
        eviction decisions. Never-touched rows don't ship: they
        re-materialize from the same deterministic per-row init.

        Tiered tables MATERIALIZE here (cold rows dequantized, listed
        oldest-first ahead of the hot LRU run — spill order IS the
        eviction order); the RSS-bounded path for big spilled tables is
        ``slab_spill.table_sections`` (what handoffs and checkpoints
        use). Gate/score state does not ride this materialized API."""
        n = len(self._index)
        ids = np.fromiter(self._index.keys(), np.int64, n)
        slots = np.fromiter(self._index.values(), np.int64, n)
        rows = (self._data[slots] if n
                else np.empty((0, self.dim), self.dtype))
        t = self._tier
        if t is not None and t.cold:
            cold = sorted(((sid, pos, r)
                           for r, (sid, pos) in t.cold.items()))
            cold_ids = np.asarray([r for _, _, r in cold], np.int64)
            cold_rows = np.empty((len(cold), self.dim), self.dtype)
            seg_cache_sid, seg_cache_rows = None, None
            for i, (sid, pos, _r) in enumerate(cold):
                if sid != seg_cache_sid:  # one read per segment
                    seg_cache_sid = sid
                    _sids, seg_cache_rows = t.store.read(sid)
                cold_rows[i] = seg_cache_rows[pos]
            ids = np.concatenate([cold_ids, ids])
            rows = np.concatenate(
                [cold_rows, np.asarray(rows, self.dtype)]) \
                if n else cold_rows
        return self.export_meta(), ids, np.ascontiguousarray(rows)

    @classmethod
    def from_state(cls, meta, ids, rows,
                   spill_path: Optional[str] = None) -> "LazyEmbeddingTable":
        tier = meta.get("tier") or {}
        kw = {}
        if tier and (tier.get("spilled") or spill_path):
            if spill_path is None:
                import tempfile
                spill_path = os.path.join(
                    tempfile.mkdtemp(prefix="pt-slab-"), "spill.log")
            kw = dict(spill_path=spill_path,
                      hot_rows=int(tier["hot_rows"]),
                      at_rest_quant=tier.get("quant", ""),
                      entry_threshold=int(tier.get("entry_threshold", 0)),
                      spill_seg_rows=int(tier.get("seg_rows", 0)),
                      track_scores=tier.get("track_scores"))
        elif tier:
            kw = dict(entry_threshold=int(tier.get("entry_threshold", 0)),
                      track_scores=tier.get("track_scores"))
        tbl = cls(height=int(meta["height"]), dim=int(meta["dim"]),
                  seed=int(meta["seed"]), scale=float(meta["scale"]),
                  max_rows=meta.get("max_rows"),
                  dtype=np.dtype(meta["dtype"]), **kw)
        tbl.import_state(ids, rows)
        tbl.evictions = int(meta.get("evictions", 0))
        return tbl

    def import_state(self, ids, rows) -> None:
        """Install a handoff snapshot wholesale (replaces any current
        content). Rows land compacted in the given order, which
        ``export_state`` guarantees is the source's LRU order. On a
        TIERED table the overflow beyond ``hot_rows`` — exactly the
        oldest prefix, i.e. the source's cold set — is written back to
        the spill tier (int8/fp16 re-encoding of already-dequantized
        values is exact, so residency round-trips are bit-identical)."""
        from collections import OrderedDict
        ids = np.asarray(ids, np.int64).reshape(-1)
        rows = np.asarray(rows, self.dtype).reshape(len(ids), self.dim)
        self._index = OrderedDict(
            (int(r), i) for i, r in enumerate(ids.tolist()))
        self._data = np.array(rows, self.dtype, copy=True)
        self._free = []
        t = self._tier
        if t is not None:
            if t.store is not None:  # wholesale replace: old log is dead
                t.store.clear()
            t.cold.clear()
            t.backing.clear()
            t.seg_live.clear()
            t.seg_cold.clear()
            t.freq.clear()
            t.scores = ({int(r): 1.0 for r in ids.tolist()}
                        if t.track_scores else {})
            self._spill_overflow()

    # -- introspection ----------------------------------------------------
    def touched_rows(self) -> int:
        """Materialized entries — hot slab rows plus spilled rows."""
        n = len(self._index)
        if self._tier is not None:
            n += len(self._tier.cold)
        return n

    def nbytes(self) -> int:
        """RESIDENT bytes (the hot slab); spilled bytes are on disk —
        see tier_stats()."""
        return len(self._index) * self.dim * self.dtype.itemsize

    def logical_params(self) -> int:
        return self.height * self.dim

    def __repr__(self):
        tier = ""
        if self._tier is not None:
            tier = (f", hot={len(self._index)}"
                    f", spilled={len(self._tier.cold)}")
        return (f"LazyEmbeddingTable(height={self.height}, dim={self.dim}, "
                f"touched={self.touched_rows()}, "
                f"evictions={self.evictions}{tier})")


class LoDRankTable:
    """reference: framework/lod_rank_table.h — sequences of one LoD level
    sorted by length descending; items are (index, length)."""

    __slots__ = ("items", "level")

    def __init__(self, items=None, level=0):
        self.items = list(items or [])  # [(seq_index, length), ...]
        self.level = level

    def __repr__(self):
        return f"LoDRankTable({self.items})"


# --------------------------------------------------------------------------
# Variable / Scope (reference: framework/variable.h:26, scope.h:46)
# --------------------------------------------------------------------------
class Variable:
    """Any-container runtime variable."""

    __slots__ = ("_holder",)

    def __init__(self):
        self._holder = None

    def get_tensor(self) -> LoDTensor:
        if self._holder is None:
            self._holder = LoDTensor()
        if not isinstance(self._holder, LoDTensor):
            raise TypeError(f"variable holds {type(self._holder).__name__}")
        return self._holder

    def get_selected_rows(self) -> SelectedRows:
        if self._holder is None:
            self._holder = SelectedRows()
        return self._holder

    def get_lod_tensor_array(self) -> LoDTensorArray:
        if self._holder is None:
            self._holder = LoDTensorArray()
        return self._holder

    def get_lod_rank_table(self) -> "LoDRankTable":
        if self._holder is None:
            self._holder = LoDRankTable()
        return self._holder

    def set_value(self, v):
        self._holder = v

    def value(self):
        return self._holder

    def is_initialized(self):
        h = self._holder
        if h is None:
            return False
        if isinstance(h, LoDTensor):
            return h.array is not None
        return True


class Scope:
    """Hierarchical name → Variable map with child scopes."""

    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, Variable] = {}
        self._parent = parent
        self._kids: List[Scope] = []
        self._lock = threading.Lock()

    def var(self, name: str) -> Variable:
        with self._lock:
            v = self._vars.get(name)
            if v is None:
                v = Variable()
                self._vars[name] = v
            return v

    def find_var(self, name: str) -> Optional[Variable]:
        s: Optional[Scope] = self
        while s is not None:
            v = s._vars.get(name)
            if v is not None:
                return v
            s = s._parent
        return None

    def erase(self, name: str):
        self._vars.pop(name, None)

    def new_scope(self) -> "Scope":
        kid = Scope(self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids.clear()

    def local_var_names(self):
        return list(self._vars.keys())

    def __contains__(self, name):
        return self.find_var(name) is not None


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def _switch_scope(scope: Scope) -> Scope:
    global _global_scope
    old = _global_scope
    _global_scope = scope
    return old


# --------------------------------------------------------------------------
# FLAGS — env-backed global config (reference: platform/flags.cc, the ~106
# gflags settable via FLAGS_* env and pybind global_value_getter_setter.cc)
# --------------------------------------------------------------------------
# Flags of the reference's GPU and CPU runtimes that ported scripts set
# and nothing here reads (allocation, threads and collectives are XLA's):
# set_flags / get_flags / the environment take them, and that is all.
_ACCEPTED_AND_IGNORED = (
    ("FLAGS_cpu_deterministic", False),
    ("FLAGS_benchmark", False),
    ("FLAGS_eager_delete_tensor_gb", 0.0),
    ("FLAGS_allocator_strategy", "xla"),
    ("FLAGS_fraction_of_gpu_memory_to_use", 1.0),
    ("FLAGS_paddle_num_threads", 1),
    ("FLAGS_use_pinned_memory", True),
    ("FLAGS_sync_nccl_allreduce", True),
)


class _GlobalFlags:
    _DEFAULTS: Dict[str, Any] = {
        **dict(_ACCEPTED_AND_IGNORED),
        "FLAGS_check_nan_inf": False,
        # what the numeric fault plane DOES when FLAGS_check_nan_inf
        # finds a non-finite step (docs/FAULT_TOLERANCE.md "Numeric
        # faults"):
        #   raise    — localize the first bad op/var and raise
        #              FloatingPointError (the reference
        #              nan_inf_utils behavior)
        #   skip     — fused discard: params/optimizer state select
        #              back to their pre-step values ON DEVICE and
        #              training continues (zero host syncs on the
        #              happy path)
        #   rollback — skip + count consecutive bad steps; after
        #              FLAGS_nan_inf_tolerance of them restore the
        #              last intact PR-3 checkpoint (bit-exact, rng
        #              counters included), at most
        #              FLAGS_nan_inf_max_rollbacks times before a
        #              typed core.NumericFaultError
        "FLAGS_nan_inf_action": "raise",
        "FLAGS_nan_inf_tolerance": 3,
        "FLAGS_nan_inf_max_rollbacks": 2,
        # pserver-side guard (VarServer/listen_and_serv): what to do
        # with a non-finite sparse grad row or dense update —
        # "" (off, apply as-is) | "drop" (discard the bad rows/update,
        # count it) | "reject" (raise NumericFaultError back to the
        # sending trainer). Trip counters ride the built-in "stats"
        # RPC under the "health" key.
        "FLAGS_ps_reject_nonfinite": "",
        # elastic PS membership plane (docs/FAULT_TOLERANCE.md "Elastic
        # membership"): replica count per pserver slot — 2 means every
        # applied update chain-forwards to a warm standby that the
        # dead-primary listener promotes, so trainers fail over instead
        # of aborting with WorkerDeadError. 1 (default) = no replication.
        "FLAGS_ps_replicas": 1,
        # how long a client-side sender (Communicator requeue, failover
        # reconnects) keeps retrying toward a slot whose primary is
        # unreachable before giving up, in seconds — covers the
        # promotion window (~2× the heartbeat timeout) with slack
        "FLAGS_ps_failover_deadline": 60.0,
        # drain: how long the source pserver waits for the in-flight
        # sync round to quiesce (pending grads applied, barrier empty)
        # before aborting the drain with the source still serving
        "FLAGS_ps_drain_quiesce_deadline": 60.0,
        # RPC fault tolerance (fluid/ps_rpc.py VarClient.call): per-call
        # deadline in MILLISECONDS (reference FLAGS_rpc_deadline), and how
        # many times a transient ConnectionError/OSError is retried with
        # exponential backoff + reconnect before surfacing
        "FLAGS_rpc_deadline": 180000,
        "FLAGS_rpc_retry_times": 3,
        # wire-framing guard: a length prefix beyond this raises
        # RpcProtocolError instead of attempting a giant allocation
        # (default 1 GiB — generous; real payloads are var-sized blobs).
        # Applies to BOTH frame parts of the binary wire (pickled header
        # and the declared raw-buffer total).
        "FLAGS_rpc_max_message_size": 1 << 30,
        # per-endpoint circuit breaker (serving ingress robustness,
        # docs/SERVING.md "Ingress & overload"): OFF by default — the
        # training planes rely on the PR 3 retry ladder + PR 6 failover
        # and must not fast-fail. Serving processes flip it on so a
        # dead pserver costs ONE deadline-bounded failure per endpoint
        # instead of every request's full retry ladder; while open,
        # calls raise CircuitOpenError immediately and the sparse path
        # serves stale cache rows flagged degraded.
        "FLAGS_rpc_circuit_breaker": False,
        # consecutive transport/worker-dead failures that trip an
        # endpoint's breaker OPEN
        "FLAGS_rpc_breaker_failures": 3,
        # how long an OPEN breaker waits before letting ONE half-open
        # probe call through (success closes it, failure re-opens)
        "FLAGS_rpc_breaker_reset_s": 5.0,
        # data-plane connection pool: how many sockets VarClient keeps
        # per endpoint so concurrent RPCs (sharded lookup fan-out,
        # communicator flushes) don't serialize on one connection
        # (reference: grpc_client.h FLAGS_rpc_client_threads /
        # channel-per-call overlap in parameter_prefetch.cc)
        "FLAGS_rpc_channels_per_endpoint": 2,
        # how long a pserver-side collective (sync barrier / reduce) waits
        # for stragglers before raising TimeoutError, in seconds; a DEAD
        # participant releases much earlier with WorkerDeadError
        "FLAGS_barrier_deadline": 300.0,
        # Communicator.stop(): how long to wait for each merge thread to
        # drain before logging a warning and moving on
        "FLAGS_communicator_join_timeout": 1.0,
        # async overlap plane (docs/PS_DATA_PLANE.md "Async overlap"):
        # how many UNACKNOWLEDGED sync rounds a trainer may keep in
        # flight while it computes ahead — the ps_round op submits the
        # round's push/barrier/pull to a background pipeline and
        # returns; a full pipe blocks the step. 0 (default) = fully
        # synchronous: the round runs inline and the trajectory is
        # bit-identical to the pre-overlap send/send_barrier/recv/
        # fetch_barrier sequence (the golden-oracle contract).
        "FLAGS_async_staleness": 0,
        # sparse prefetch under the overlap plane: while window i
        # computes, a background thread pulls window i+1's embedding
        # rows into a per-step buffer the lookup op consumes without an
        # RPC. Only active when FLAGS_async_staleness > 0 (prefetched
        # rows are up to one round stale by construction).
        "FLAGS_sparse_prefetch": True,
        # ---- compressed PS data plane (docs/PS_DATA_PLANE.md
        # "Compression") ----
        # wire v3 payload quantization: "" (off, exact frames) | "fp16"
        # (downcast) | "int8" (per-row absmax scale). Lossy and OPT-IN;
        # applies only to float32 data-plane payloads on connections
        # that negotiated wire v3 in the _hello handshake — old peers
        # on either side keep exchanging exact frames. Bytes-saved
        # evidence scrapes as ps_wire_bytes_{raw,sent}_total.
        "FLAGS_ps_wire_quant": "",
        # DGC deep gradient compression (reference WITH_DGC; Lin et
        # al., ICLR 2018): dense grads on the sync send / ps_round /
        # geo-delta paths sparsify to their top-k elements with local
        # error-feedback accumulation — unsent mass stays in the
        # trainer's residual and ships later, so convergence follows
        # the full gradient. OFF by default: bit-identical behavior.
        "FLAGS_dgc": False,
        # final sparsity: fraction of elements DROPPED per push (0.999
        # = ship the top 0.1%, the paper's steady-state setting)
        "FLAGS_dgc_sparsity": 0.999,
        # momentum correction factor for the compressor's local
        # velocity accumulation (u = m*u + g; 0 disables — pair with
        # a momentum-free server optimizer to keep semantics plain SGD)
        "FLAGS_dgc_momentum": 0.0,
        # warm-up: over the first N pushes per grad the sparsity ramps
        # exponentially from ~75% toward FLAGS_dgc_sparsity (the
        # paper's epoch ramp, per-push); 0 = no warm-up
        "FLAGS_dgc_warmup_steps": 0,
        # grads smaller than this many elements ship dense — top-k
        # bookkeeping on a bias vector costs more than it saves
        "FLAGS_dgc_min_elements": 512,
        # static-analysis plane (docs/ANALYSIS.md; fluid/analysis.py):
        # verify Programs at the choke points — Executor first compile of
        # a program version, the transpiler's own trainer-program output,
        # tools/verify_program.py. "" (off, default) | "warn" (log each
        # diagnostic + program_verify_diagnostics_total{rule,severity}
        # counters) | "error" (additionally raise ProgramVerifyError on
        # error-severity diagnostics). Runs ONCE per program version —
        # never per step, so warn mode adds no steady-state cost.
        "FLAGS_program_verify": "",
        "FLAGS_executor_mode": "compiled",   # compiled | interpreted
        # segmented compilation: when a block fails the all-or-nothing
        # compiled check (a stateful/host op like auc/print/read among
        # pure ops), partition it into jitted segments around interpreted
        # islands instead of interpreting EVERYTHING (fluid/executor.py
        # _SegmentedBlock, fluid/ir.py analyze_block_segments). OFF means
        # such blocks take the pure interpreter (the correctness oracle).
        "FLAGS_executor_segmentation": True,
        # don't bother jitting segments for tiny blocks: below this many
        # compilable ops the per-segment dispatch + compile overhead
        # exceeds the interpreter's per-op cost
        "FLAGS_executor_seg_min_ops": 8,
        "FLAGS_seed": 0,
        # bf16 inputs on MXU matmuls/convs with f32 accumulate (params and
        # activations stay f32 outside the unit) — the TPU-native analogue
        # of the reference's TF32/fp16 math modes
        "FLAGS_use_bf16_matmul": False,
        # sparse tables with at least this many elements are hosted as
        # init-on-touch LazyEmbeddingTable on pservers (beyond-HBM scale)
        "FLAGS_lazy_sparse_table_threshold": 1 << 26,
        # ---- capacity tier (docs/PS_DATA_PLANE.md "Capacity tier") ----
        # non-empty: pserver lazy tables grow a DISK tier — LRU overflow
        # of the hot set spills to an mmap-backed CRC-stamped segment
        # log under this directory and promotes back on touch. Empty
        # (default) = pure in-RAM slab, bit-identical to the pre-tier
        # behavior.
        "FLAGS_ps_slab_spill_dir": "",
        # rows pinned hot in RAM per table when the spill tier is on
        # (the table's entire RAM bound; must be > 0 with a spill dir)
        "FLAGS_ps_slab_hot_rows": 0,
        # at-rest row encoding in the spill log: "" (raw table dtype) |
        # "fp16" | "int8" (per-row absmax scales — the PR 11 wire codec
        # reused AT REST, ~3.6x row density at embedding widths; lossy,
        # error bound absmax_row/254 per element per spill cycle).
        # Segments holding non-finite rows store raw so
        # dequant-on-touch surfaces the poison to
        # FLAGS_ps_reject_nonfinite exactly.
        "FLAGS_ps_at_rest_quant": "",
        # frequency-gated entry creation (reference PSLib entry gating):
        # an id must be PULLED this many times before it materializes a
        # slot; grads for unentered ids are dropped+counted. 0/1 = off.
        "FLAGS_ps_entry_threshold": 0,
        # eviction write-back batch bound: one spill-log segment holds
        # at most this many rows (one segment read serves a whole cold
        # batch — the I/O fan-in unit)
        "FLAGS_ps_slab_seg_rows": 4096,
        # track per-row touch scores even without the entry gate or the
        # spill tier, so the table_shrink admin RPC works (costs one
        # dict update per touched row; gating implies it). On an
        # untiered, un-bounded table this is the ONLY cost of making it
        # shrinkable. Ignored for max_rows-bounded tables (LRU owns
        # their eviction).
        "FLAGS_ps_slab_track_scores": False,
        # trainer-driven shrink cron (reference PSLib save/shrink cron):
        # every N of trainer 0's sync rounds it fires ONE table_shrink
        # admin RPC per pserver (decay/threshold below), so idle rows
        # decay out of gated/tiered tables without an operator in the
        # loop; 0 = off. Counted server-side as slab "shrink_runs".
        "FLAGS_ps_shrink_every_steps": 0,
        "FLAGS_ps_shrink_decay": 0.98,
        "FLAGS_ps_shrink_threshold": 0.5,
        # opt-in persistent XLA executable cache: non-empty -> every
        # Executor routes compiles through
        # jax_compilation_cache_dir=<dir> (inference.enable_compile_cache)
        # so a SECOND process running the same program loads the
        # executable from disk instead of recompiling
        "FLAGS_compilation_cache_dir": "",
        # multiprocess DataLoader liveness probe: how long the consumer
        # waits on the batch queue before checking whether the worker
        # process died (a killed worker surfaces RuntimeError instead of
        # hanging forever); per-loader kwarg worker_timeout overrides
        "FLAGS_dataloader_worker_timeout": 5.0,
        # how long to wait for the worker process to exit at iterator
        # teardown before it is killed
        "FLAGS_dataloader_join_timeout": 5.0,
        # ---- unified telemetry plane (docs/OBSERVABILITY.md) ----
        # non-empty: every process streams its profiler spans into a
        # bounded chrome-trace shard <dir>/trace-<pid>.json (raw
        # monotonic timestamps + clock-offset metadata from the ps_rpc
        # _hello handshake); tools/timeline.py merge aligns the shards
        # into ONE clock-corrected cluster timeline keyed by trace id.
        # Spans record even without start_profiler() while this is set.
        "FLAGS_trace_dir": "",
        # ring-buffer bound of one trace shard — oldest events drop
        # (counted in the shard metadata) so a long run's shard stays
        # O(bound), not O(steps)
        "FLAGS_trace_shard_max_events": 65536,
        # in-memory profiler event bound (ring semantics): beyond this
        # the OLDEST events drop and a dropped-events counter surfaces
        # in the summary/snapshot — a long profiled run can no longer
        # grow the host heap without bound. Applied at start_profiler/
        # reset_profiler time.
        "FLAGS_profiler_max_events": 1_000_000,
        # opt-in lightweight /metrics sidecar (Prometheus text format
        # over the telemetry registry): >0 binds 127.0.0.1:<port> at
        # pserver/ingress/executor startup so the chaos/loadgen tools
        # scrape instead of poking process internals.
        # 0 (default) = off; the serving ingress additionally always
        # serves GET /metrics on its own port.
        "FLAGS_metrics_port": 0,
    }

    def __init__(self):
        self._values: Dict[str, Any] = {}
        for k, dv in self._DEFAULTS.items():
            env = os.environ.get(k)
            self._values[k] = self._parse(env, dv) if env is not None else dv

    @staticmethod
    def _parse(s: str, like: Any):
        if isinstance(like, bool):
            return s.lower() in ("1", "true", "yes")
        if isinstance(like, int):
            return int(s)
        if isinstance(like, float):
            return float(s)
        return s

    def __getitem__(self, key):
        return self._values[key]

    def __setitem__(self, key, value):
        self._values[key] = value

    def __contains__(self, key):
        return key in self._values

    def keys(self):
        return self._values.keys()


globals_ = _GlobalFlags()


def get_flag(name: str):
    return globals_[name]


def set_flag(name: str, value):
    globals_[name] = value


def set_flags(d: Dict[str, Any]):
    for k, v in d.items():
        globals_[k] = v
