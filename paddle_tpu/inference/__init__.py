"""Inference engine (reference: paddle/fluid/inference/ —
AnalysisPredictor analysis_predictor.cc:288, AnalysisConfig
api/analysis_config.cc, ZeroCopyTensor, C API capi/).

TPU inversion of the reference pipeline: the reference loads a
ProgramDesc, runs ~30 IR fusion passes, optionally captures TensorRT/Lite
subgraphs, then interprets with NaiveExecutor (analysis_predictor.cc:497,
:235). Here the load step jits the whole pruned program once — operator
fusion, layout and memory planning are XLA's; the "TensorRT engine"
becomes the XLA executable itself, and warmup/compile caching replaces
subgraph capture.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

__all__ = ["Config", "AnalysisConfig", "Predictor", "AnalysisPredictor",
           "create_predictor", "create_paddle_predictor", "PredictTensor",
           "PassStrategy", "PredictorPool", "enable_compile_cache"]


_COMPILE_CACHE_DIR = None


def enable_compile_cache(cache_dir: str) -> str:
    """Point XLA's persistent compilation cache at ``cache_dir`` — the
    TPU-native role of the reference's serialized TensorRT engine cache
    (analysis_config.cc SetOptimCacheDir + tensorrt/ engine
    serialization): a SECOND process loading the same model skips the
    XLA compile entirely (the executable is loaded from disk, keyed by
    HLO hash). Process-global; idempotent per dir. Every compile in the
    process benefits (training steps included), which matches how the
    engine cache removes the reference's cold-start.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, whoever runs the process
    has placed the cache: that directory is used and the argument is
    not. The directory is part of the cache's key, so one that moves
    never hits. Returns the directory in use."""
    global _COMPILE_CACHE_DIR
    import os
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = os.path.abspath(placed or cache_dir)
    if _COMPILE_CACHE_DIR == cache_dir:
        return cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every executable: the defaults skip small/fast compiles,
    # which is exactly the cold-start this exists to remove
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not placed:
        # LRU-bound the directory: programs change every commit and
        # orphaned HLO-keyed entries would otherwise accumulate forever
        # (a cache placed from outside is sized from outside too:
        # JAX_COMPILATION_CACHE_MAX_SIZE)
        jax.config.update("jax_compilation_cache_max_size",
                          4 * 1024 * 1024 * 1024)
    # jax initializes the cache module LAZILY at the first compile and
    # never re-reads the config after that — enabling the cache in a
    # process that already compiled anything (a predictor created after
    # model-building ran, the serving cold-start shape) was a silent
    # no-op: zero entries ever written. Reset so the NEXT compile picks
    # the directory up.
    compilation_cache.reset_cache()
    _COMPILE_CACHE_DIR = cache_dir
    return cache_dir


class AnalysisConfig:
    """reference: api/paddle_analysis_config.h. GPU/MKLDNN/TensorRT knobs
    are accepted and recorded; on TPU they map to one compiled executable,
    so they only gate diagnostics."""

    def __init__(self, model_dir: Optional[str] = None,
                 params_file: Optional[str] = None):
        self._model_dir = model_dir
        self._prog_file = None
        self._params_file = params_file
        self._prog_bytes = None
        self._params_bytes = None
        self._ir_optim = True
        self._use_feed_fetch_ops = False
        self._enable_memory_optim = True
        self._tensorrt = False
        self._device = "tpu"
        self._bf16 = False
        self._profile = False
        self._pass_builder = None
        self._optim_cache_dir = None

    # --- model location ---------------------------------------------------
    def set_model(self, model_dir, params_file=None):
        self._model_dir = model_dir
        self._params_file = params_file

    def set_model_buffer(self, prog_bytes: bytes, params_bytes: bytes):
        """Serve a model from in-memory byte buffers — the reference's
        SetModelBuffer path (analysis_config.cc SetModelBuffer), used by
        services that ship models over the wire. The bytes are the
        standard serialized ProgramDesc + save_combine stream."""
        self._prog_bytes = bytes(prog_bytes)
        self._params_bytes = bytes(params_bytes)

    def model_from_memory(self) -> bool:
        return self._prog_bytes is not None

    def set_optim_cache_dir(self, cache_dir: str):
        """reference analysis_config.cc SetOptimCacheDir — on TPU this
        activates the persistent XLA executable cache (see
        enable_compile_cache): later processes loading this model skip
        the compile."""
        self._optim_cache_dir = cache_dir

    def set_prog_file(self, f):
        self._prog_file = f

    def set_params_file(self, f):
        self._params_file = f

    def model_dir(self):
        return self._model_dir

    # --- toggles (parity surface) ----------------------------------------
    def switch_ir_optim(self, flag=True):
        self._ir_optim = bool(flag)

    def switch_use_feed_fetch_ops(self, flag=True):
        self._use_feed_fetch_ops = bool(flag)

    def enable_memory_optim(self, flag=True):
        self._enable_memory_optim = bool(flag)

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._device = "tpu"  # single accelerator backend on this build

    def disable_gpu(self):
        self._device = "cpu"

    def enable_tensorrt_engine(self, **kwargs):
        """TensorRT subgraphs ≈ the jitted XLA executable; recorded only."""
        self._tensorrt = True

    def tensorrt_engine_enabled(self):
        return self._tensorrt

    def switch_specify_input_names(self, flag=True):
        pass

    def specify_input_name(self):
        return True

    def enable_bf16(self):
        """bf16 inference (the reference's enable_mkldnn_bfloat16 /
        TRT-fp16 role): matmuls/convs run MXU-native bf16."""
        self._bf16 = True

    def bf16_enabled(self):
        return self._bf16

    def enable_profile(self):
        self._profile = True

    def pass_builder(self) -> "PassStrategy":
        """Customizable IR pass pipeline (reference: PaddlePassBuilder,
        api/paddle_pass_builder.h) — mutations here change which passes
        the predictor applies at load."""
        if self._pass_builder is None:
            from paddle_tpu.fluid.ir import INFERENCE_PASSES
            self._pass_builder = PassStrategy(list(INFERENCE_PASSES))
        return self._pass_builder


class PassStrategy:
    """reference: paddle_pass_builder.h PaddlePassBuilder."""

    def __init__(self, passes: List[str]):
        self._passes = list(passes)

    def all_passes(self) -> List[str]:
        return list(self._passes)

    def append_pass(self, name: str):
        from paddle_tpu.fluid.ir import get_pass
        get_pass(name)  # validate it exists
        self._passes.append(name)

    def insert_pass(self, idx: int, name: str):
        from paddle_tpu.fluid.ir import get_pass
        get_pass(name)
        self._passes.insert(idx, name)

    def delete_pass(self, name: str):
        self._passes = [p for p in self._passes if p != name]


Config = AnalysisConfig


class PredictTensor:
    """Zero-copy style handle (reference: ZeroCopyTensor
    inference/api/details/zero_copy_tensor.cc)."""

    def __init__(self, predictor: "AnalysisPredictor", name: str,
                 is_input: bool):
        self._p = predictor
        self.name = name
        self._is_input = is_input

    def copy_from_cpu(self, arr: np.ndarray):
        if not self._is_input:
            raise RuntimeError(f"'{self.name}' is an output tensor")
        self._p._inputs[self.name] = np.asarray(arr)

    def copy_to_cpu(self) -> np.ndarray:
        if self._is_input:
            raise RuntimeError(f"'{self.name}' is an input tensor")
        return np.asarray(self._p._outputs[self.name])

    def reshape(self, shape):
        pass  # shapes flow from copy_from_cpu

    @property
    def lod(self):
        return self._p._output_lods.get(self.name, [])


class AnalysisPredictor:
    """reference: analysis_predictor.cc:288 Run / :235 PrepareExecutor."""

    def __init__(self, config: AnalysisConfig, _shared=None):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import core
        self.config = config
        if config._optim_cache_dir:
            enable_compile_cache(config._optim_cache_dir)
        self._exe = fluid.Executor()
        if _shared is not None:
            # weight-sharing clone (reference AnalysisPredictor::Clone
            # shares the params scope across predictors serving threads)
            (self._scope, self._program, self._feed_names,
             self._fetch_names) = _shared
        elif config.model_from_memory():
            self._scope = core.Scope()
            self._program, self._feed_names, self._fetch_names = \
                self._load_from_memory(config)
            self._optimize(config)
        else:
            self._scope = core.Scope()
            with fluid.scope_guard(self._scope):
                (self._program, self._feed_names,
                 fetch_targets) = fluid.io.load_inference_model(
                     config.model_dir(), self._exe,
                     model_filename=config._prog_file,
                     params_filename=config._params_file)
            self._fetch_names = [v.name for v in fetch_targets]
            self._optimize(config)
        if config._bf16:
            core.set_flag("FLAGS_use_bf16_matmul", True)
        self._inputs: Dict[str, np.ndarray] = {}
        self._outputs: Dict[str, np.ndarray] = {}
        self._output_lods: Dict[str, list] = {}

    def _load_from_memory(self, config):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import core
        from paddle_tpu.fluid.framework import Program
        from paddle_tpu.fluid.io import _deserialize_lod_tensor_stream
        prog = Program.parse_from_string(config._prog_bytes)
        block = prog.global_block()
        persistables = sorted(
            v.name for v in block.vars.values()
            if v.persistable and v.name not in ("feed", "fetch"))
        tensors = _deserialize_lod_tensor_stream(config._params_bytes,
                                                 len(persistables))
        for name, t in zip(persistables, tensors):
            self._scope.var(name).set_value(t)
        feed_names = [v.name for v in block.vars.values()
                      if getattr(v, "need_check_feed", False)
                      or getattr(v, "is_data", False)]
        written, written_order = set(), []
        for op in block.ops:
            for n in op.output_arg_names:
                if n not in written:
                    written.add(n)
                    written_order.append(n)
        consumed = set()
        for op in block.ops:
            consumed.update(op.input_arg_names)
        # program order, not set order: output position must be stable
        # across processes (clients index Predictor.run results)
        fetch_names = [n for n in written_order
                       if n not in consumed
                       and block.vars.get(n) is not None
                       and not block.vars[n].persistable]
        return prog, feed_names, fetch_names

    def _optimize(self, config):
        if not config._ir_optim:
            return
        # reference AnalysisPredictor::OptimizeInferenceProgram
        # (analysis_predictor.cc:497): canonicalise + fuse with the
        # param scope so conv+bn folding can rewrite weights; the
        # model's fetch targets are protected from fusion. A customized
        # config.pass_builder() overrides the canonical pipeline.
        from paddle_tpu.fluid.ir import INFERENCE_PASSES, PassManager
        names = (config._pass_builder.all_passes()
                 if config._pass_builder is not None
                 else INFERENCE_PASSES)
        pm = PassManager(names, scope=self._scope)
        self._program = pm.apply(self._program, for_test=True,
                                 protected=self._fetch_names)

    # --- interface --------------------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_output_names(self) -> List[str]:
        return list(self._fetch_names)

    def get_input_handle(self, name) -> PredictTensor:
        if name not in self._feed_names:
            raise KeyError(f"unknown input '{name}'")
        return PredictTensor(self, name, True)

    def get_output_handle(self, name) -> PredictTensor:
        if name not in self._fetch_names:
            raise KeyError(f"unknown output '{name}'")
        return PredictTensor(self, name, False)

    # reference AnalysisPredictor::Run — one call, feeds set beforehand
    def run(self, inputs: Optional[List[np.ndarray]] = None):
        import paddle_tpu.fluid as fluid
        if inputs is not None:
            for name, arr in zip(self._feed_names, inputs):
                self._inputs[name] = np.asarray(arr)
        missing = [n for n in self._feed_names if n not in self._inputs]
        if missing:
            raise KeyError(f"inputs not set: {missing}")
        with fluid.scope_guard(self._scope):
            fetched = self._exe.run(self._program, feed=dict(self._inputs),
                                    fetch_list=self._fetch_names,
                                    return_numpy=False)
        self._outputs = {}
        self._output_lods = {}
        for n, t in zip(self._fetch_names, fetched):
            self._outputs[n] = np.asarray(t.array)
            self._output_lods[n] = t.lod()
        return [self._outputs[n] for n in self._fetch_names]

    def get_input_tensor_shape(self) -> Dict[str, List[int]]:
        block = self._program.global_block()
        return {n: list(getattr(block.vars.get(n), "shape", ()) or ())
                for n in self._feed_names}

    def try_shrink_memory(self):
        """Drop cached executables (reference TryShrinkMemory); the
        next run re-jits."""
        self._exe._compiled_cache.clear()

    def clone(self, share_weights: bool = True) -> "AnalysisPredictor":
        """Reference Clone(): the clone serves from the SAME params scope
        (zero weight duplication) with its own feed/fetch state."""
        if share_weights:
            return AnalysisPredictor(
                self.config, _shared=(self._scope, self._program,
                                      list(self._feed_names),
                                      list(self._fetch_names)))
        return AnalysisPredictor(self.config)


Predictor = AnalysisPredictor


class PredictorPool:
    """reference: api/paddle_inference_api.h PredictorPool — one loaded
    predictor cloned per serving slot, weights shared."""

    def __init__(self, config: AnalysisConfig, size: int = 1):
        first = AnalysisPredictor(config)
        self._preds = [first] + [first.clone() for _ in range(size - 1)]

    def retrieve(self, idx: int) -> AnalysisPredictor:
        return self._preds[idx]

    def size(self) -> int:
        return len(self._preds)


def create_predictor(config: AnalysisConfig) -> AnalysisPredictor:
    return AnalysisPredictor(config)


create_paddle_predictor = create_predictor
