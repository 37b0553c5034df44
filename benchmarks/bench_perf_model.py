"""Paper §6.2 (Figs. 6/7/8): performance-model validation.

Runs the REAL paged engine on a reduced model across a grid of batch shapes,
fits Eqs. 1-3 to the measured iteration times, and reports the max relative
prediction error (the paper claims <10% on A100/V100; we measure on this
host's CPU — the functional forms, not the coefficients, are the claim)."""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np

from repro.configs import get_arch, reduced
from repro.core.perf_model import DecodeModel, KVModel, PrefillModel
from repro.models.model import LM
from repro.serving.engine import EngineConfig, PagedEngine


def _median_time(fn, n=5) -> float:
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run(verbose: bool = True) -> List[Dict]:
    # Eq. 2's linear regime requires the O(s*d^2) projections to dominate the
    # O(s^2*d) attention — true for real models (s <~ d); the reduced model
    # must preserve that, so keep d_model wide relative to the test lengths.
    arch = reduced(get_arch("llama2-13b"), n_layers=2, d_model=512,
                   vocab=256, n_heads=8, n_kv_heads=8, d_ff=2048)
    model = LM(arch)
    params = model.init(jax.random.key(0))
    eng = PagedEngine(arch, params, EngineConfig(
        max_batch=16, page_size=16, n_pages=1024, max_pages_per_seq=64))

    rows = []
    # --- Fig 6: prefill time vs total input length (batch-size invariant) ---
    # sizes share one attention code path (dense: all % kv_chunk != 0)
    xs, ts = [], []
    f = eng._prefill_jit
    for s in (192, 320, 448, 576):
        toks = np.random.default_rng(0).integers(2, arch.vocab, (1, s))
        import jax.numpy as jnp
        args = (params, jnp.asarray(toks), s - 1)
        f(*args)[0].block_until_ready()                # compile
        dt = _median_time(lambda: f(*args)[0].block_until_ready())
        xs.append(s)
        ts.append(dt)
    pm = PrefillModel.fit(xs, ts)
    pred = pm(xs)
    err_pre = float(np.max(np.abs(pred - np.asarray(ts))
                           / np.maximum(ts, 1e-9)))
    rows.append({"name": "fig6_prefill_linear_fit",
                 "us_per_call": float(np.mean(ts)) * 1e6,
                 "derived": f"max_rel_err={err_pre:.3f};k1={pm.k1:.2e}"})

    # --- Fig 7: decode time vs (batch, total context) -----------------------
    import jax.numpy as jnp
    bs, cs, ts2 = [], [], []
    for b in (1, 2, 4, 8, 16):
        for ctx in (64, 256, 512):
            lengths = np.zeros((16,), np.int32)
            lengths[:b] = ctx
            bt = np.zeros((16, 64), np.int32)
            pages_per = max(ctx // 16 + 1, 1)
            pid = 1
            for i in range(b):
                for j in range(pages_per):
                    bt[i, j] = pid
                    pid += 1
            active = np.zeros((16,), bool)
            active[:b] = True
            tokens = np.full((16,), 3, np.int64)
            args = (params, eng.kv_k, eng.kv_v, jnp.asarray(bt),
                    jnp.asarray(lengths), jnp.asarray(tokens),
                    jnp.asarray(active))
            eng._decode_jit(*args)[0].block_until_ready()
            dt = _median_time(
                lambda: eng._decode_jit(*args)[0].block_until_ready())
            bs.append(b)
            cs.append(b * ctx)
            ts2.append(dt)
    dm = DecodeModel.fit(bs, cs, ts2)
    pred = dm(bs, cs)
    err_dec = float(np.max(np.abs(pred - np.asarray(ts2))
                           / np.maximum(ts2, 1e-9)))
    rows.append({"name": "fig7_decode_bilinear_fit",
                 "us_per_call": float(np.mean(ts2)) * 1e6,
                 "derived": f"max_rel_err={err_dec:.3f};k2={dm.k2:.2e};"
                            f"c2={dm.c2:.2e};c3={dm.c3:.2e}"})

    # --- Fig 8: KV bytes vs context (exact bookkeeping) ---------------------
    toks = np.arange(1, 512, 37)
    kvb = toks * arch.kv_bytes_per_token(dtype_bytes=4) / 2
    km = KVModel.fit(toks, kvb)
    err_kv = float(np.max(np.abs(km(toks) - kvb) / np.maximum(kvb, 1e-9)))
    rows.append({"name": "fig8_kv_linear_fit", "us_per_call": 0.0,
                 "derived": f"max_rel_err={err_kv:.4f};h={km.h:.1f}"})

    if verbose:
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    return rows


if __name__ == "__main__":
    run()
