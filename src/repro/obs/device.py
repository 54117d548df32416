"""The program's names on the JAX profiler: host spans and device scopes.

``repro.obs``'s tracer covers simulated time.  Work on the accelerator is
recorded by the JAX profiler alone, and this module only names it (DESIGN.md
§13):

* host spans (``jax.profiler.TraceAnnotation``) around the serving and
  training loops of ``repro.launch``, each carrying its counts as arguments,
  and one around every Python garbage collection while the profiler is on;
* named scopes (``jax.named_scope``) inside the model and the train step,
  which set the ``op_name`` of the HLO they emit and change nothing that is
  computed.

Nothing is recorded here.  With the profiler off a span costs one
``TraceAnnotation`` enter and exit (its arguments are formatted only when the
profiler is on), and the collection hook one check per collection.

JAX is imported lazily and ``repro.obs`` does not import this module, so the
simulators never load JAX.
"""

from __future__ import annotations

import gc
import itertools

# host spans of launch/serve.generate
SERVE_GENERATE = "serve.generate"  # call, batch, prompt, output
SERVE_PREFILL = "serve.prefill"  # batch, prompt
SERVE_DECODE = "serve.decode"  # batch, output
SERVE_STEP = "serve.step"  # step (0 .. prompt + output - 1)
SERVE_H2D = "serve.h2d"  # one prompt token copied to the device
SERVE_FETCH = "serve.fetch"  # served tokens joined and copied to the host
# host spans of launch/train.train_loop; each iteration is also a profiler
# step named TRAIN_STEPS (jax.profiler.StepTraceAnnotation), for xprof's
# step view
TRAIN_STEPS = "train"
TRAIN_BATCH = "train.batch"  # step
TRAIN_PLACE = "train.place"
TRAIN_STEP = "train.step"
# a Python garbage collection, while the profiler is on
HOST_GC = "host.gc"  # generation, collected
SPAN_PREFIXES = ("serve.", "train.", "host.")

# named scopes (HLO op_name path components)
EMBED = "embed"
ATTENTION = "attention"  # projections, rope, cache write, attention, output projection
MLP = "mlp"  # the MLP or MoE block
NORM = "norm"  # the layers' norms
UNEMBED = "unembed"  # final norm and unembedding
LAYERS = "layers"  # the layer scan's own ops: per-layer slices in, stacked outputs out
LOSS = "loss"
GRAD_SYNC = "grad_sync"
OPTIMIZER = "optimizer"
SCOPES = (EMBED, ATTENTION, MLP, NORM, UNEMBED, LAYERS, LOSS, GRAD_SYNC, OPTIMIZER)
# named scopes nested in those above, not in SCOPES: a reader takes them by
# name (chipbench's ``Reading.per_step_ms_under``)
LATENT = "latent"  # under attention: latent attention's W_kv_a, its norm and W_kv_b
MOE = "moe"  # under mlp: the whole expert layer (the scopes below, the loop over choices)
ROUTER = "router"  # under moe: router logits, scores, top-k choice, gates, balance loss
DISPATCH = "dispatch"  # under moe: (token, choice) pairs sorted by held expert, rows gathered
EXPERTS = "experts"  # under moe: the held experts' grouped matmuls
SHARED_EXPERTS = "shared_experts"  # under moe: the MLP every token passes through
COMBINE = "combine"  # under moe: expert rows back to their tokens, weighted by the gates

_calls = itertools.count()
_profiler = None


def _jax_profiler():
    global _profiler
    if _profiler is None:
        import jax.profiler

        _profiler = jax.profiler
    return _profiler


def span(name: str, **counts):
    """A host span on the profiler's trace, with ``counts`` as its arguments."""
    return (_profiler or _jax_profiler()).TraceAnnotation(name, **counts)


def next_call() -> int:
    """Number of this process's next ``serve.generate`` call."""
    return next(_calls)


class _GcSpan:
    """``gc.callbacks`` hook: a HOST_GC span over each collection that
    starts while the profiler is on."""

    def __init__(self):
        self.open = None

    def __call__(self, phase, info):
        if phase == "start":
            annotation = (_profiler or _jax_profiler()).TraceAnnotation
            if annotation.is_enabled():
                self.open = annotation(HOST_GC, generation=info["generation"])
                self.open.__enter__()
        elif self.open is not None:
            done, self.open = self.open, None
            done.set_metadata(collected=info["collected"])
            done.__exit__(None, None, None)


_gc_span = _GcSpan()


def trace_gc() -> None:
    """Span every garbage collection while the profiler is on (idempotent)."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
