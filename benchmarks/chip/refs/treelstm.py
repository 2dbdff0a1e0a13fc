"""Plain reference for the binary (constituency) Tree-LSTM of Tai, Socher
and Manning 2015 (arXiv:1503.00075, section 3.2, N = 2) with a softmax
classifier's logits at every node.

Written from the configuration alone (``configs/<config>.json``): the
weights are redrawn from the seed in the order the configuration states,
and the forward pass is a recursion over the tree in ``jax.numpy``, float32,
one node at a time. Nothing of the program under test is imported.

A tree is a leaf's token id (``int``) or a pair ``(left, right)``. A leaf
follows section 3.2's equations with no children: ``i, o, u`` from its
embedding, ``c = i * u``, ``h = o * tanh(c)``. An internal node reads only
its children: every gate from ``[h_left; h_right]``, one forget gate per
child, ``c = f_l * c_left + f_r * c_right + i * u``. The logits of each
node are ``h W + b``. :func:`forward` returns them for every node in
post-order (left subtree, right subtree, the node), the order in which the
served graph emits its output nodes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LEAF_GATES = "iog"
INTERNAL_GATES = ("i", "fl", "fr", "o", "g")


def _bf16(x):
    """Round float32 to bfloat16's 8-bit mantissa, staying float32.
    ``reduce_precision`` and not a pair of casts: XLA may drop a
    float32 -> bfloat16 -> float32 round trip as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def matmul(a, b, precision: str):
    """``a @ b`` in float32 at ``"highest"``, or at ``"high"``: three bf16
    products (hi*hi + hi*lo + lo*hi, each operand split into a bf16 head
    and a bf16 remainder) accumulated in float32, as the MXU's three-pass
    mode computes it. Written out, so the lower precision is the same on
    every backend."""
    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(a, b, precision=hp)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    return (jnp.matmul(a_hi, b_hi, precision=hp)
            + jnp.matmul(a_hi, b_lo, precision=hp)
            + jnp.matmul(a_lo, b_hi, precision=hp))


def weights(cfg: dict, seed: int) -> dict:
    """The configuration's weights, redrawn from ``seed`` in its order:
    0.1 times a standard normal for the embedding ``(vocab, embed)``, the
    leaf's ``W_i, b_i, W_o, b_o, W_g, b_g``, the internal node's ``W_i,
    b_i, W_fl, b_fl, W_fr, b_fr, W_o, b_o, W_g, b_g``, then the head's
    ``(hidden, classes)``; the head's bias is zero. Device arrays, so the
    recursion moves no weight per node."""
    rng = np.random.default_rng(seed)
    E, H, C, V = cfg["embed"], cfg["hidden"], cfg["classes"], cfg["vocab"]
    w = {"table": 0.1 * rng.standard_normal((V, E))}
    for g in LEAF_GATES:
        w["leaf_W_" + g] = 0.1 * rng.standard_normal((E, H))
        w["leaf_b_" + g] = 0.1 * rng.standard_normal(H)
    for g in INTERNAL_GATES:
        w["int_W_" + g] = 0.1 * rng.standard_normal((2 * H, H))
        w["int_b_" + g] = 0.1 * rng.standard_normal(H)
    w["head_W"] = 0.1 * rng.standard_normal((H, C))
    w["head_b"] = np.zeros(C)
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


@partial(jax.jit, static_argnames=("precision",))
def _leaf(w, tok, precision):
    x = w["table"][tok]
    y = {g: matmul(x, w["leaf_W_" + g], precision) + w["leaf_b_" + g]
         for g in LEAF_GATES}
    c = jax.nn.sigmoid(y["i"]) * jnp.tanh(y["g"])
    h = jax.nn.sigmoid(y["o"]) * jnp.tanh(c)
    return h, c, matmul(h, w["head_W"], precision) + w["head_b"]


@partial(jax.jit, static_argnames=("precision",))
def _internal(w, hl, cl, hr, cr, precision):
    hh = jnp.concatenate([hl, hr])
    y = {g: matmul(hh, w["int_W_" + g], precision) + w["int_b_" + g]
         for g in INTERNAL_GATES}
    c = (jax.nn.sigmoid(y["fl"]) * cl + jax.nn.sigmoid(y["fr"]) * cr
         + jax.nn.sigmoid(y["i"]) * jnp.tanh(y["g"]))
    h = jax.nn.sigmoid(y["o"]) * jnp.tanh(c)
    return h, c, matmul(h, w["head_W"], precision) + w["head_b"]


def forward(w: dict, tree, precision: str = "highest") -> np.ndarray:
    """Every node's logits, ``(nodes, classes)``, in post-order. Every
    matmul names its precision; the default is pinned to ``highest`` as
    well, so nothing runs at the TPU's float32 default of one bf16 pass."""
    logits: list = []

    def visit(t):
        if isinstance(t, tuple):
            hl, cl = visit(t[0])
            hr, cr = visit(t[1])
            h, c, y = _internal(w, hl, cl, hr, cr, precision)
        else:
            h, c, y = _leaf(w, jnp.int32(t), precision)
        logits.append(y)
        return h, c

    with jax.default_matmul_precision("highest"):
        visit(tree)
        return np.asarray(jnp.stack(logits))
