"""Predictor (reference: AnalysisPredictor in
paddle/fluid/inference/api/analysis_predictor.cc + the paddle_infer handle
API: get_input_names/get_input_handle/run/get_output_handle).

The predictor wraps either (a) a Layer instance (direct, the common in-process
path) or (b) a jit.save'd artifact directory. forward is jit-compiled once per
input signature — XLA's AOT compile IS the reference's pass pipeline.
"""
import numpy as np

from ..framework.core import Tensor, to_tensor


class _IOHandle:
    """Zero-copy style tensor handle (reference: ZeroCopyTensor)."""

    def __init__(self, name):
        self.name = name
        self._value = None

    def reshape(self, shape):
        self._shape = tuple(shape)

    def copy_from_cpu(self, arr):
        self._value = np.ascontiguousarray(arr)

    def share_external_data(self, arr):
        self._value = arr

    def copy_to_cpu(self):
        v = self._value
        if isinstance(v, Tensor):
            return np.asarray(v.numpy())
        return np.asarray(v)

    def shape(self):
        v = self._value
        return list(np.shape(v.numpy() if isinstance(v, Tensor) else v))


class Predictor:
    def __init__(self, config_or_layer, input_names=None):
        from ..nn.layer.layers import Layer

        self._jitted = {}
        if isinstance(config_or_layer, Layer):
            self._layer = config_or_layer
            self._layer.eval()
        else:
            config = config_or_layer
            # artifact path: a jit.save'd Layer is weights + descriptor; a
            # Layer instance must be supplied to bind them (the reference
            # deserializes a Program; our program is the traced Layer)
            raise ValueError(
                "create_predictor(Config) from serialized artifacts requires "
                "the model class; pass the Layer directly: "
                "create_predictor(layer) or Predictor(layer). For jit.save'd "
                "weights, build the Layer, layer.set_state_dict(paddle.jit."
                "load(path)['state_dict']), then Predictor(layer)."
            )
        self._input_names = list(input_names) if input_names else ["x"]
        self._inputs = {n: _IOHandle(n) for n in self._input_names}
        self._outputs = {}

    # -- handle API --------------------------------------------------------
    def get_input_names(self):
        return list(self._input_names)

    def get_input_handle(self, name):
        return self._inputs[name]

    def get_output_names(self):
        return list(self._outputs)

    def get_output_handle(self, name):
        return self._outputs[name]

    # -- execution ---------------------------------------------------------
    def run(self, inputs=None):
        """Either positional (list of np arrays, paddle_infer v2 style) or via
        previously-filled input handles."""
        if inputs is not None:
            arrs = [np.asarray(a) for a in inputs]
        else:
            arrs = [self._inputs[n]._value for n in self._input_names]

        sig = tuple((a.shape, str(a.dtype)) for a in arrs)
        fn = self._jitted.get(sig)
        if fn is None:
            from ..jit_api import StaticLayer

            fn = StaticLayer(self._layer)
            self._jitted[sig] = fn
        out = fn(*[to_tensor(a) for a in arrs])
        outs = out if isinstance(out, (tuple, list)) else [out]
        self._outputs = {}
        results = []
        for i, o in enumerate(outs):
            h = _IOHandle(f"out_{i}")
            h._value = o
            self._outputs[h.name] = h
            results.append(np.asarray(o.numpy()) if isinstance(o, Tensor) else np.asarray(o))
        return results if inputs is not None else None

    # -- generation --------------------------------------------------------
    def generate(self, input_ids, **kwargs):
        """Autoregressive decode via the model's jitted KV-cache loop
        (GenerationMixin) — reference: AnalysisPredictor-driven generation."""
        if not hasattr(self._layer, "generate"):
            raise TypeError(f"{type(self._layer).__name__} has no generate()")
        out = self._layer.generate(to_tensor(input_ids), **kwargs)
        return np.asarray(out.numpy())

    def generate_speculative(self, input_ids, draft_model, **kwargs):
        """Draft-verify decoding through the predictor (exactly the target
        model's greedy stream; see GenerationMixin.generate_speculative)."""
        draft = draft_model._layer if isinstance(draft_model, Predictor) else draft_model
        out = self._layer.generate_speculative(to_tensor(input_ids), draft, **kwargs)
        return np.asarray(out.numpy())

    def serve(self, prompts, max_new_tokens=32, eos_token_id=None,
              max_seqs=4, page_size=64, num_pages=None, max_len=None,
              engine=None, **serve_kwargs):
        """Continuous-batching greedy serving over the paged KV pool
        (inference.continuous.ContinuousBatchingEngine): variable-length
        prompts queue, join mid-flight as slots/pages free, and each result
        equals that prompt's dense generate(). Pass `engine` to reuse a warm
        engine (compiled step programs + pool) across calls."""
        from .continuous import ContinuousBatchingEngine

        if engine is None:
            if max_len is None:
                longest = max(len(np.asarray(p).reshape(-1)) for p in prompts)
                # the longest prompt's full decode extent, in whole pages
                max_len = -(-(longest + max_new_tokens) // page_size) \
                    * page_size
            engine = ContinuousBatchingEngine(
                self._layer, max_seqs=max_seqs, page_size=page_size,
                num_pages=num_pages, max_len=max_len)
        # sampling knobs / on_token streaming pass straight through
        return engine.serve(prompts, max_new_tokens, eos_token_id=eos_token_id,
                            **serve_kwargs)

    # -- AOT export (reference: save_optimized_model / Program serialization;
    # TPU-native: StableHLO via jax.export — the compiled artifact is
    # hardware-portable and reloadable without the model class) ------------
    def export_aot(self, path, *example_inputs):
        """Trace + lower the forward on example inputs and serialize the
        StableHLO artifact to `path`. Returns the byte count."""
        import jax
        from jax import export as jexport

        layer = self._layer
        state = layer.raw_state_dict()

        def pure(state, *args):
            out = layer.functional_call(
                {k: Tensor(v, stop_gradient=True) for k, v in state.items()},
                *[Tensor(a) for a in args],
                training=False,
            )
            outs = out if isinstance(out, (tuple, list)) else (out,)
            return tuple(o._data if isinstance(o, Tensor) else o for o in outs)

        args = tuple(to_tensor(a)._data for a in example_inputs)
        from ..observability import compilemem as _compilemem

        with _compilemem.record_compile("predictor.export_aot",
                                        trigger="aot"):
            exp = jexport.export(jax.jit(pure))(state, *args)  # compile-ledger-ok
        data = exp.serialize()
        with open(path, "wb") as f:
            f.write(data)
        self._aot = (exp, state)
        return len(data)

    @staticmethod
    def load_aot(path):
        """Load a serialized AOT artifact; returns AotPredictor (call with
        the same state pytree + inputs signature used at export)."""
        from jax import export as jexport

        with open(path, "rb") as f:
            exp = jexport.deserialize(bytearray(f.read()))
        return AotPredictor(exp)

    def clone(self):
        return Predictor(self._layer, self._input_names)


class AotPredictor:
    """Runs a deserialized StableHLO export: state-free serving — the weights
    travel as the first pytree argument (reference: the deserialized
    inference Program + persistables)."""

    def __init__(self, exported):
        self._exported = exported

    def run(self, state, *inputs):
        args = tuple(to_tensor(a)._data for a in inputs)
        out = self._exported.call(state, *args)
        return [np.asarray(o) for o in (out if isinstance(out, (tuple, list)) else [out])]


def create_predictor(config_or_layer, input_names=None):
    return Predictor(config_or_layer, input_names)
