"""Inference engine: Predictor + AOT-compiled export.

Parity: /root/reference/paddle/fluid/inference/api/{analysis_predictor.h:82
AnalysisPredictor, paddle_inference_api.h PaddlePredictor} and the
freeze-and-deploy flow around save_inference_model (inference/api/api_impl
.cc).  The reference freezes a pruned GraphDef, runs analysis passes, and
serves through a C++ predictor.  TPU-native shape: the pruned Program
lowers to ONE jitted XLA computation with the parameters baked in as
constants ("freeze"), and `jax.export` serializes the compiled StableHLO
so a server process can deserialize and run it without Python tracing,
retracing, or the original model code — the analogue of shipping the
analysis-pass output as a deployable artifact.
"""

import json
import os

import numpy as np

import jax
import jax.numpy as jnp

# jax.export is a submodule that `import jax` does not import
from jax import export as _jax_export

from . import flags
from .core.dtype import to_jax_dtype
from .framework.executor import _RngBox, interpret
from .framework.program import Program

_COMPILED_FILE = "__compiled__.jaxexport"


def _make_pure_fn(program, fetch_names, params):
    """Pure feeds->fetches function over the pruned program: parameters
    enter as closure constants (frozen), stochastic ops get a fixed key
    (inference programs are is_test; the key only exists for signature
    compatibility)."""
    ops = list(program.global_block().ops)

    def fn(feeds):
        env = dict(params)
        env.update(feeds)
        interpret(ops, env, _RngBox(jax.random.PRNGKey(0)))
        return [env[n] for n in fetch_names]

    return fn


class Predictor:
    """Serve a saved inference model (AnalysisPredictor analogue).

    p = Predictor(dirname)            # from save_inference_model output
    outs = p.run({"x": batch})        # list of np.ndarray, one per fetch
    """

    def __init__(self, dirname, model_filename=None, params_filename=None):
        with open(os.path.join(dirname,
                               model_filename or "__model__.json")) as f:
            model = json.load(f)
        self._program = Program.from_json(json.dumps(model["program"]))
        self._feed_names = list(model["feed_names"])
        self._fetch_names = list(model["fetch_names"])
        data = np.load(os.path.join(dirname,
                                    params_filename or "__params__.npz"))
        persist = {v.name for v in self._program.list_vars()
                   if v.persistable}
        self._params = {n: jnp.asarray(data[n]) for n in data.files
                        if n in persist}
        # Graph-optimizer folding path (FLAGS_inference_fold): fold
        # test-mode batch_norms into conv/fc weights, collapse
        # scale/identity chains, and DCE from the fetch set — the
        # reference's inference analysis passes, applied once at load
        # time so BOTH the compiled and the degraded (run_eager) paths
        # serve the same folded program.  Outputs are allclose, not
        # bitwise, vs the unfolded program.
        self._fold_report = None
        if flags.flag("inference_fold"):
            from . import passes as _passes

            self._program, params, self._fold_report = \
                _passes.fold_inference(
                    self._program, self._params,
                    fetch_names=self._fetch_names,
                    program_key="predictor:%s" % os.path.basename(
                        os.path.abspath(dirname)))
            self._params = {n: jnp.asarray(v) for n, v in params.items()}
        # the un-jitted pure fn is kept addressable: the serving
        # runtime's degraded mode (run_eager) interprets through it
        # when the compiled path is circuit-broken
        self._pure_fn = _make_pure_fn(self._program, self._fetch_names,
                                      self._params)
        self._fn = jax.jit(self._pure_fn)

    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return list(self._fetch_names)

    def prepare_feed(self, feed):
        """Validate + device-cast one feed dict to the program's feed
        dtypes: {name: jnp array}.  Shared by run(), run_eager() and
        the serving runtime's micro-batcher (which pads PREPARED feeds,
        so a padded batch is bitwise the same arrays a direct run
        sees)."""
        feeds = {}
        for name in self._feed_names:
            if name not in feed:
                raise KeyError(f"missing feed '{name}'")
            v = self._program.global_block()._find_var_recursive(name)
            dtype = to_jax_dtype(v.dtype) if v is not None and v.dtype \
                else None
            feeds[name] = jnp.asarray(np.asarray(feed[name]), dtype=dtype)
        return feeds

    def feed_specs(self):
        """{feed name: (feature_shape, jax dtype)} — the per-example
        trailing dims (leading batch dim stripped; None entries for
        dynamic trailing dims) the serving bucketer shapes its padded
        buckets from."""
        specs = {}
        for name in self._feed_names:
            v = self._program.global_block()._find_var_recursive(name)
            shape = tuple(v.shape) if v is not None and v.shape \
                else None
            dtype = to_jax_dtype(v.dtype) if v is not None and v.dtype \
                else jnp.float32
            feat = None
            if shape:
                feat = tuple(None if d in (None, -1) else int(d)
                             for d in shape[1:])
            specs[name] = (feat, dtype)
        return specs

    def run(self, feed):
        """feed: dict name -> ndarray. Returns [np.ndarray] per fetch."""
        outs = self._fn(self.prepare_feed(feed))
        return [np.asarray(o) for o in outs]

    def run_eager(self, feed):
        """Interpret the pruned program op-by-op WITHOUT jit — no
        tracing, no compile cache, works at any batch shape.  Slow, but
        immune to compiled-path failures: the serving runtime's
        degraded mode routes here while its circuit breaker is open."""
        outs = self._pure_fn(self.prepare_feed(feed))
        return [np.asarray(o) for o in outs]

    # -- AOT --------------------------------------------------------------

    def export_compiled(self, feed_shapes, dirname=None,
                        platforms=None):
        """AOT-compile for concrete feed shapes and serialize the
        StableHLO artifact (the deployable executable the reference gets
        from its analysis passes + engine serialization).

        feed_shapes: dict name -> example ndarray OR (shape, dtype).
        Returns the artifact path.
        """
        examples = {}
        for n, spec in feed_shapes.items():
            if isinstance(spec, tuple) and len(spec) == 2 \
                    and isinstance(spec[0], (list, tuple)):
                shape, dtype = spec
                examples[n] = jnp.zeros(tuple(shape), to_jax_dtype(dtype))
            else:
                examples[n] = jnp.asarray(np.asarray(spec))
        exported = _jax_export.export(
            self._fn, platforms=platforms)(examples)
        blob = exported.serialize()
        path = os.path.join(dirname or ".", _COMPILED_FILE)
        with open(path, "wb") as f:
            f.write(blob)
        return path


class CompiledPredictor:
    """Run a serialized AOT artifact: no Program, no model code, no
    retracing — deserialize + call (the deployment side of the reference's
    C++ inference engine)."""

    def __init__(self, path):
        if os.path.isdir(path):
            path = os.path.join(path, _COMPILED_FILE)
        with open(path, "rb") as f:
            self._exported = _jax_export.deserialize(f.read())
        self._path = path

    @property
    def in_avals(self):
        return self._exported.in_avals

    def run(self, feed):
        feeds = {n: jnp.asarray(np.asarray(v)) for n, v in feed.items()}
        outs = self._exported.call(feeds)
        return [np.asarray(o) for o in outs]


def save_compiled_inference_model(dirname, feed_shapes, model_filename=None,
                                  params_filename=None, platforms=None):
    """Freeze + AOT-compile a saved inference model directory in place.

    Call after io.save_inference_model; adds __compiled__.jaxexport next
    to the JSON/npz artifacts so deployment can use CompiledPredictor."""
    p = Predictor(dirname, model_filename, params_filename)
    return p.export_compiled(feed_shapes, dirname, platforms=platforms)
