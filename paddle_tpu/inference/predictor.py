"""Predictor implementation (reference: AnalysisPredictor —
paddle/fluid/inference/api/analysis_predictor.cc; Python surface
paddle.inference.Config/create_predictor)."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from ..enforce import (InvalidArgumentError,
                       PreconditionNotMetError, enforce)

__all__ = ["Config", "Predictor", "PredictorTensor", "create_predictor"]


class Config:
    """Deploy configuration (reference: AnalysisConfig). Switches that XLA
    owns natively (IR passes, memory optim, TensorRT) are accepted and
    recorded for API parity but have no effect."""

    def __init__(self, model_path: Optional[str] = None,
                 params_path: Optional[str] = None):
        # jit.save writes <path>.stablehlo + <path>.pdiparams; accept either
        # the bare prefix or the .stablehlo file
        if model_path and model_path.endswith(".stablehlo"):
            model_path = model_path[: -len(".stablehlo")]
        self._model_path = model_path
        self._params_path = params_path
        self._device = "tpu"
        self._device_id = 0
        self._precision = "float32"
        self._switches: Dict[str, bool] = {}

    # -- model ---------------------------------------------------------------
    def set_model(self, model_path: str, params_path: Optional[str] = None):
        if model_path.endswith(".stablehlo"):
            model_path = model_path[: -len(".stablehlo")]
        self._model_path = model_path
        self._params_path = params_path

    def model_path(self) -> Optional[str]:
        return self._model_path

    # -- device --------------------------------------------------------------
    def enable_use_gpu(self, memory_pool_init_size_mb: int = 100,
                       device_id: int = 0):
        # GPU request maps to the accelerator backend (TPU here)
        self._device, self._device_id = "tpu", device_id

    def enable_tpu(self, device_id: int = 0):
        self._device, self._device_id = "tpu", device_id

    def disable_gpu(self):
        self._device = "cpu"

    def use_gpu(self) -> bool:
        return self._device == "tpu"

    def device(self):
        devs = jax.devices()
        accel = [d for d in devs if d.platform != "cpu"]
        if self._device == "tpu" and accel:
            return accel[min(self._device_id, len(accel) - 1)]
        cpus = [d for d in devs if d.platform == "cpu"] or devs
        return cpus[0]

    # -- precision / passes ---------------------------------------------------
    def _noop(self, name, note):
        import warnings
        if name not in self._switches:
            warnings.warn(f"Config.{name}: no effect on TPU — {note}",
                          stacklevel=3)

    def enable_memory_optim(self, *a, **kw):
        """Satisfied structurally: the predictor's inputs/outputs are
        device-resident handles and XLA owns buffer lifetimes (no
        analysis-pass memory planner to switch on)."""
        self._switches["memory_optim"] = True

    def switch_ir_optim(self, flag: bool = True):
        """Satisfied structurally: XLA always runs its optimization
        pipeline; there is no unoptimized executor to fall back to."""
        self._switches["ir_optim"] = flag

    def enable_mkldnn(self):
        self._noop("mkldnn", "oneDNN is an x86 CPU library; the CPU "
                   "fallback here is XLA:CPU")
        self._switches["mkldnn"] = True

    def set_cpu_math_library_num_threads(self, n: int):
        self._noop("cpu_threads", "XLA:CPU sizes its own thread pool; set "
                   "XLA_FLAGS=--xla_cpu_multi_thread_eigen / taskset "
                   "at process level")
        self._switches["cpu_threads"] = n

    def enable_bf16(self):
        """Real effect: the predictor casts floating inputs to bfloat16
        before execution (MXU-native inference precision)."""
        self._precision = "bfloat16"

    def enable_int8(self):
        """Real effect: a live Layer callable gets its Linear sublayers
        converted to W8A8 QuantizedLinear (int8 MXU execution — the
        reference's TensorRT-int8 deploy path; its rate against bf16 is
        not measured on the current installation). jit.save artifacts
        must be re-exported already-quantized."""
        self._precision = "int8"

    def enable_profile(self):
        """Real effect: each run() executes inside a paddle_tpu.profiler
        record scope; retrieve with paddle_tpu.profiler exports."""
        self._switches["profile"] = True

    def profile_enabled(self) -> bool:
        return self._switches.get("profile", False)

    def precision(self) -> str:
        return self._precision

    def summary(self) -> str:
        return (f"Config(model={self._model_path}, device={self._device}:"
                f"{self._device_id}, precision={self._precision})")


class PredictorTensor:
    """Zero-copy-style handle (reference: ZeroCopyTensor). copy_from_cpu
    places data on the predictor's device; copy_to_cpu fetches results."""

    def __init__(self, name: str, device, spec=None):
        self.name = name
        self._device = device
        self._spec = spec  # (shape, dtype) expected by the program
        self._value: Optional[jax.Array] = None

    def reshape(self, shape: Sequence[int]):
        pass  # shapes are fixed by the exported program

    def copy_from_cpu(self, data: np.ndarray):
        if self._spec is not None:
            shape, dtype = self._spec
            data = np.ascontiguousarray(data, dtype=dtype)
            if tuple(data.shape) != tuple(shape):
                raise InvalidArgumentError(
                    f"input '{self.name}' expects shape {tuple(shape)}, "
                    f"got {tuple(data.shape)}")
        self._value = jax.device_put(data, self._device)

    def share_external_data(self, array):
        """Adopt an already-device-resident array without a copy."""
        self._value = array

    def copy_to_cpu(self) -> np.ndarray:
        enforce(self._value is not None,
                f"tensor '{self.name}' is empty", op="Tensor.copy_to_cpu",
                error=PreconditionNotMetError)
        return np.asarray(jax.device_get(self._value))

    @property
    def shape(self):
        if self._value is not None:
            return tuple(self._value.shape)
        return tuple(self._spec[0]) if self._spec else None


class Predictor:
    """Loads a jit.save artifact (or wraps a live callable), AOT-compiles
    for the configured device, and runs with device-resident handles."""

    def __init__(self, config: Config, fn=None, num_inputs: int = None):
        self.config = config
        self._device = config.device()
        if fn is not None:
            from ..nn.layer.layers import Layer as _Layer
            if config.precision() == "int8" and isinstance(fn, _Layer):
                from ..quantization import convert_to_int8
                fn = convert_to_int8(fn)
            self._callable = fn
            self._in_specs = None
            if num_inputs is None:
                import inspect
                try:
                    num_inputs = sum(
                        1 for p in inspect.signature(fn).parameters.values()
                        if p.default is inspect.Parameter.empty
                        and p.kind in (p.POSITIONAL_ONLY,
                                       p.POSITIONAL_OR_KEYWORD))
                except (TypeError, ValueError):
                    num_inputs = 1
            self._n_in = max(num_inputs, 1)
        else:
            enforce(config.model_path(), "Config has no model path",
                    op="create_predictor",
                    error=PreconditionNotMetError)
            from ..jit import load as jit_load
            tl = jit_load(config.model_path())
            self._callable = tl
            self._in_specs = [(s.shape, s.dtype) for s in tl.input_spec]
            self._out_specs = [(s.shape, s.dtype) for s in tl.output_spec]
            self._n_in = len(self._in_specs)
        n_in = self._n_in
        self._inputs: Dict[str, PredictorTensor] = {
            f"input_{i}": PredictorTensor(
                f"input_{i}", self._device,
                self._in_specs[i] if self._in_specs else None)
            for i in range(n_in)}
        self._outputs: Dict[str, PredictorTensor] = {}

    # -- reference surface ---------------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._inputs)

    def get_input_handle(self, name: str) -> PredictorTensor:
        return self._inputs[name]

    def get_output_names(self) -> List[str]:
        return list(self._outputs) or ["output_0"]

    def get_output_handle(self, name: str) -> PredictorTensor:
        return self._outputs[name]

    def run(self, inputs: Optional[Sequence[np.ndarray]] = None):
        """Either positional `inputs` or previously-filled input handles."""
        if inputs is not None:
            if len(inputs) != len(self._inputs):
                raise InvalidArgumentError(
                    f"got {len(inputs)} inputs but the program has "
                    f"{len(self._inputs)} input slots "
                    f"({list(self._inputs)}); fill handles individually for "
                    f"partial feeding, or pass num_inputs= to Predictor for "
                    f"callables with defaulted params you want to feed")
            for h, a in zip(self._inputs.values(), inputs):
                h.copy_from_cpu(np.asarray(a))
        args = []
        # live callables retrace freely; a jit.save artifact pins its input
        # avals at export time, so casting would break the exported calling
        # convention — re-export the model in bf16 to deploy bf16 there
        cast = (jnp.bfloat16 if (self.config.precision() == "bfloat16"
                                 and self._in_specs is None)
                else None)
        if (self.config.precision() == "bfloat16"
                and self._in_specs is not None
                and not getattr(self, "_warned_bf16", False)):
            import warnings
            warnings.warn(
                "enable_bf16() has no effect on a jit.save artifact (its "
                "input dtypes are pinned at export); re-export the model "
                "with bfloat16 inputs to deploy bf16")
            self._warned_bf16 = True
        for name, h in self._inputs.items():
            enforce(h._value is not None, f"input '{name}' not set",
                    op="Predictor.run", error=PreconditionNotMetError)
            v = h._value
            if cast is not None and jnp.issubdtype(v.dtype, jnp.floating):
                v = v.astype(cast)
            args.append(v)
        if self.config.profile_enabled():
            from ..profiler import RecordEvent
            with RecordEvent("predictor.run"):
                out = self._callable(*args)
        else:
            out = self._callable(*args)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        self._outputs = {}
        results = []
        for i, o in enumerate(outs):
            t = PredictorTensor(f"output_{i}", self._device)
            t.share_external_data(o)
            self._outputs[f"output_{i}"] = t
            results.append(np.asarray(jax.device_get(o)))
        return results

    def clear_intermediate_tensor(self):
        pass  # XLA owns buffer lifetimes


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)
