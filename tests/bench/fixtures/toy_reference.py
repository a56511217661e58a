"""A toy reference for the harness's tests: a configuration whose layers
run their MLP twice (attention, then the same MLP as two residual
sub-blocks).  It is written as a reference of another block would be:
it imports the dense reference and writes only its block."""
import functools

import jax

import reference
from reference import plane_qmax  # noqa: F401  (the harness calls it)


@functools.partial(jax.jit, static_argnames=("model_items", "qmax"))
def _layer(w, x, model_items, qmax):
    model = dict(model_items)
    with jax.default_matmul_precision("highest"):
        x = x + reference.attention(w, reference.rmsnorm(x), model, qmax)
        x = x + reference.mlp(w, reference.rmsnorm(x), model, qmax)
        return x + reference.mlp(w, reference.rmsnorm(x), model, qmax)


def logit_gaps(seed, model, seqs, starts, length, qmax, control_qmax=None):
    return reference.logit_gaps(seed, model, seqs, starts, length, qmax,
                                control_qmax, block=_layer)
