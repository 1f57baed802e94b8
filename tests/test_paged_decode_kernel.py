"""Plain-pool paged decode-attention Pallas kernel (interpret mode) vs the
gather path `layers.attn_core_paged` over the same pool and block tables.

Cases of one test: the qwen1.5-0.5b head shape (G = 1, D = 64) and the
deepseek-coder-33b one (G = 7, D = 128) at reduced head counts; lengths
0, 1, BS, BS+1 and full capacity beside random ones; rows sharing
aliased prefix blocks; trash-block holes under a traced sliding window;
and a pool whose every block past the rows' live ranges, the trash block
included, holds NaN, which the kernel must never read into a result."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.models.layers import attn_core_paged

BS = 16
MB = 6                                  # capacity 96 tokens


def _case(hkv, g, d, seed, *, window=None, nan_dead=False):
    """(q, clean pool, pool the kernel reads, tables, lens) for 8 rows."""
    rng = np.random.default_rng(seed)
    cap = MB * BS
    lens = np.asarray([0, 1, BS, BS + 1, cap, *rng.integers(2, cap, 3)],
                      np.int32)
    b = len(lens)
    nb = 1 + b * MB
    k = rng.standard_normal((nb, BS, hkv * d)).astype(np.float16)
    v = rng.standard_normal((nb, BS, hkv * d)).astype(np.float16)
    ids = list(rng.permutation(np.arange(1, nb)))
    tables = np.zeros((b, MB), np.int32)
    shared = ids[:2]                    # a two-block prefix rows 4-6 share
    for r, n in enumerate(lens):
        nblk = -(-int(n) // BS)
        own = shared[:min(nblk, 2)] if r in (4, 5, 6) else []
        row = own + [ids.pop() for _ in range(nblk - len(own))]
        tables[r, :nblk] = row
    if window is not None:
        # blocks wholly before every row's window were slid back to the
        # trash block, as the engine leaves them
        for r, n in enumerate(lens):
            for j in range(MB):
                if (j + 1) * BS <= n - window:
                    tables[r, j] = 0
    q = rng.standard_normal((b, hkv * g, d)).astype(np.float32)
    kk, vv = k.copy(), v.copy()
    if nan_dead:
        live = {int(t) for r, n in enumerate(lens)
                for t in tables[r, :-(-int(n) // BS)]}
        dead = [i for i in range(nb) if i not in live or i == 0]
        kk[dead] = np.nan
        vv[dead] = np.nan
    return q, (k, v), (kk, vv), tables, lens


@pytest.mark.parametrize("case", [
    dict(hkv=4, g=1, d=64),
    dict(hkv=2, g=7, d=128),
    dict(hkv=4, g=1, d=64, window=40),
    dict(hkv=2, g=7, d=128, window=20),
    dict(hkv=4, g=1, d=64, nan_dead=True),
    dict(hkv=2, g=7, d=128, nan_dead=True),
], ids=["g1d64", "g7d128", "g1d64-window", "g7d128-window",
        "g1d64-nan-dead", "g7d128-nan-dead"])
def test_matches_gather_path(case):
    hkv, g, d = case["hkv"], case["g"], case["d"]
    window = case.get("window")
    q, (k, v), (kk, vv), tables, lens = _case(
        hkv, g, d, seed=hkv * 100 + g + d, window=window,
        nan_dead=case.get("nan_dead", False))
    wa = None if window is None else jnp.asarray([window], jnp.int32)
    # the pool as the engine stores it: f16 bit patterns as int16
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kk.view(np.int16)),
        jnp.asarray(vv.view(np.int16)),
        jnp.asarray(tables), jnp.asarray(lens), window_arr=wa,
        interpret=True))
    # the gather path over the clean pool, as layers.attention runs it
    phys = (tables[:, :, None] * BS + np.arange(BS)).reshape(len(lens), -1)
    kc = k.reshape(-1, hkv, d)[phys]
    vc = v.reshape(-1, hkv, d)[phys]
    want = np.asarray(attn_core_paged(
        jnp.asarray(q)[:, None], jnp.asarray(kc), jnp.asarray(vc),
        q_offset=jnp.asarray(np.maximum(lens - 1, 0)),
        kv_len=jnp.asarray(lens), window=window))[:, 0]
    assert np.isfinite(got).all()
    live = lens > 0
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)
    # a row with no keys reads at most one block and returns zeros
    assert (got[~live] == 0).all()
