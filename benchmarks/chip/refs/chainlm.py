"""Plain reference for the chain LM: one LSTM layer, embedding, output head.

Written from the configuration alone (``configs/<config>.json``): the
weights are redrawn from the seed by the recipe the configuration states,
and the forward pass is straightforward ``jax.numpy`` in float32. Nothing
of the program under test is imported.

The served sequence of a request is its prompt, left-padded with the pad
token to the next power of two (at least ``prompt_bucket_min``), followed
by the tokens it was served. The first served token is read from the
logits after the last prompt token; each later one from the logits after
the token before it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

GATES = "ifgo"


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
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    hp = jax.lax.Precision.HIGHEST
    return (jnp.matmul(a_hi, b_hi, precision=hp)
            + jnp.matmul(a_hi, b_lo, precision=hp)
            + jnp.matmul(a_lo, b_hi, precision=hp))


def weights(cfg: dict, seed: int) -> dict:
    """The configuration's weights, redrawn from ``seed`` in its order."""
    rng = np.random.default_rng(seed)
    E, H, V = cfg["embed"], cfg["hidden"], cfg["vocab"]
    w = {"table": 0.1 * rng.standard_normal((V, E)),
         "wo": 0.1 * rng.standard_normal((H, V)),
         "bo": np.zeros(V)}
    for g in GATES:
        w["W_" + g] = 0.1 * rng.standard_normal((E + H, H))
        w["b_" + g] = 0.1 * rng.standard_normal(H)
    return {k: np.asarray(v, np.float32) for k, v in w.items()}


def padded_prompt(prompt: list[int], cfg: dict) -> list[int]:
    n = len(prompt)
    lb = max(cfg["prompt_bucket_min"], 1 << max(n - 1, 0).bit_length())
    return [cfg["pad_token"]] * (lb - n) + list(prompt)


def _cell(w, tok, h, c, precision):
    """One LSTM step of a batch: token ids ``tok`` (B,), state (B, H) ->
    the new state and the logits after it."""
    H = w["wo"].shape[0]
    wx = jnp.concatenate([w["W_" + g] for g in GATES], axis=1)
    bx = jnp.concatenate([w["b_" + g] for g in GATES])
    x = w["table"][tok]
    y = matmul(jnp.concatenate([x, h], -1), wx, precision) + bx
    i = jax.nn.sigmoid(y[:, :H])
    f = jax.nn.sigmoid(y[:, H:2 * H])
    g = jnp.tanh(y[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(y[:, 3 * H:])
    c2 = f * c + i * g
    h2 = o * jnp.tanh(c2)
    return h2, c2, matmul(h2, w["wo"], precision) + w["bo"]


@partial(jax.jit, static_argnames=("precision",))
def _forward(w, toks, lengths, pick, precision):
    """toks, pick (B, T) int32, lengths (B,) -> after each token the best
    logit, the token that has it, and the logit of ``pick`` there, each
    (B, T); and (h, c) after the first ``lengths[b]`` tokens. The logits
    are reduced inside the step, so no (B, T, V) array is ever held."""
    B = toks.shape[0]
    H = w["wo"].shape[0]

    def step(carry, inp):
        h, c = carry
        tok, p, t = inp
        h2, c2, logits = _cell(w, tok, h, c, precision)
        out = (logits.max(axis=1), jnp.argmax(logits, axis=1),
               jnp.take_along_axis(logits, p[:, None], axis=1)[:, 0])
        live = (t < lengths)[:, None]
        return (jnp.where(live, h2, h), jnp.where(live, c2, c)), out

    zero = jnp.zeros((B, H), jnp.float32)
    T = toks.shape[1]
    (h, c), outs = jax.lax.scan(step, (zero, zero),
                                (toks.T, pick.T, jnp.arange(T)))
    return tuple(o.T for o in outs) + (h, c)


@partial(jax.jit, static_argnames=("precision",))
def _one_step(w, tok, h, c, precision):
    h2, c2, _ = _cell(w, tok, h, c, precision)
    return h2, c2


def step(w: dict, toks: list[int], h, c, precision: str = "highest"):
    """One step from a given state: each row's token ``toks[b]`` fed into
    the cell from ``(h[b], c[b])``. Returns the new ``(h, c)`` as np
    arrays. Rows are padded to a bucket so a run compiles one program."""
    n = len(toks)
    B = _bucket(n, 8)
    tok = np.zeros(B, np.int32)
    tok[:n] = toks
    hh = np.zeros((B, h.shape[1]), np.float32)
    cc = np.zeros((B, c.shape[1]), np.float32)
    hh[:n], cc[:n] = h, c
    h2, c2 = _one_step(w, tok, hh, cc, precision)
    return np.asarray(h2)[:n], np.asarray(c2)[:n]


def _bucket(n: int, floor: int) -> int:
    return max(floor, 1 << max(n - 1, 0).bit_length())


def forward(w: dict, seqs: list[list[int]], precision: str = "highest",
            picks: list[list[int]] | None = None):
    """Run each sequence through the model. Returns ``(best, first, picked,
    h, c)``: for each sequence, after each of its tokens, the best logit,
    the token that has it, and the logit of the token ``picks`` names at
    that position (token 0 where ``picks`` is not given); and the state
    after its last token (np ``(n, H)`` each). Sequences are padded to
    fixed buckets so a run compiles one program."""
    n = len(seqs)
    T = _bucket(max(len(s) for s in seqs), 64)
    B = _bucket(n, 8)
    toks = np.zeros((B, T), np.int32)
    pick = np.zeros((B, T), np.int32)
    lengths = np.zeros(B, np.int32)
    for b, s in enumerate(seqs):
        toks[b, :len(s)] = s
        lengths[b] = len(s)
        if picks is not None:
            pick[b, :len(picks[b])] = picks[b]
    best, first, picked, h, c = (np.asarray(a) for a in
                                 _forward(w, toks, lengths, pick, precision))
    cut = [(best[b, :len(s)], first[b, :len(s)], picked[b, :len(s)])
           for b, s in enumerate(seqs)]
    return ([x[0] for x in cut], [x[1] for x in cut], [x[2] for x in cut],
            h[:n], c[:n])
