"""What the check of ``correct`` shares across model families.

A family's float32 reference (``bench/families/<name>.py``, ``logit_rows``)
is written from the configuration file alone and imports nothing of the
program. It builds on the helpers here: products at float32 ``HIGHEST``
precision on weights upcast from bfloat16 (``_mm``), RMSNorm, rotate-half
rotary embeddings, and causal grouped-query attention in blocks of queries.

``mode="int8"`` swaps the float32 products of ``_mm`` for the control's
lower precision: activations rounded per row and weights per output column
to 8-bit integers. ``served_readings`` runs a family's reference over the
served sequences and reads the gaps that ``correct`` compares.
"""
from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _int8(x, axis):
    """Round ``x`` to int8 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, mode):
    w = w.astype(jnp.float32)
    if mode == "int8":
        x, w = _int8(x, -1), _int8(w, 0)
    elif mode != "f32":
        raise ValueError(f"no reference mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta, rot):
    """x: (T, H, hd); rotate-half on the first ``rot`` dims."""
    if rot == 0:
        return x
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv            # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _attention(q, k, v, q_block):
    """Causal GQA attention of q (T, Hq, hd) on k, v (T, Hkv, hd)."""
    t, hq, hd = q.shape
    hkv = k.shape[1]
    qg = q.reshape(t // q_block, q_block, hkv, hq // hkv, hd)
    kpos = jnp.arange(t)

    def block(args):
        i, qb = args                                  # (Bq, Hkv, G, hd)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HIGHEST)
        s = s * hd ** -0.5
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(t // q_block), qg))
    return out.reshape(t, hq * hd)


@jax.jit
def gaps(ref_rows, ids):
    """How far each chosen token's reference logit lies below the best."""
    picked = jnp.take_along_axis(ref_rows, ids[:, None], axis=1)[:, 0]
    return jnp.max(ref_rows, axis=1) - picked


def _bucket(n: int, top: int, q_block: int) -> int:
    b = q_block
    while b < n:
        b *= 2
    return min(b, -(-top // q_block) * q_block)


class Served(NamedTuple):
    tokens: List[int]     # prompt followed by the served tokens
    n_prompt: int


def served_readings(logit_rows: Callable, params, cfg: dict,
                    seqs: Sequence[Served], modes: Sequence[str] = ()
                    ) -> Dict[str, np.ndarray]:
    """Per served token: ``served`` is the gap of the token the program
    served; each of ``modes`` the gap of the token that mode's own logits
    put first, at the same positions. All gaps are read from the float32
    reference, the family's ``logit_rows``."""
    ref = cfg["reference"]
    q_block, top = ref["q_block"], ref["max_tokens"]
    out: Dict[str, List[np.ndarray]] = {"served": []}
    for m in modes:
        out[m] = []
    for s in seqs:
        served = np.asarray(s.tokens[s.n_prompt:], np.int32)
        n = len(served)
        t = _bucket(len(s.tokens) - 1, top, q_block)
        toks = np.zeros((t,), np.int32)
        toks[:len(s.tokens) - 1] = s.tokens[:-1]
        m_pad = _bucket(n, ref["max_served"], 8)
        rows = np.zeros((m_pad,), np.int32)
        rows[:n] = np.arange(s.n_prompt - 1, s.n_prompt - 1 + n)
        ids = np.zeros((m_pad,), np.int32)
        ids[:n] = served
        f32 = logit_rows(params, toks, rows, cfg=cfg, mode="f32",
                         q_block=q_block)
        out["served"].append(np.asarray(gaps(f32, ids))[:n])
        for m in modes:
            low = logit_rows(params, toks, rows, cfg=cfg, mode=m,
                             q_block=q_block)
            first = jnp.argmax(low, axis=1).astype(jnp.int32)
            out[m].append(np.asarray(gaps(f32, first))[:n])
            del low
        del f32
    return {k: (np.concatenate(v) if v else np.zeros((0,)))
            for k, v in out.items()}
