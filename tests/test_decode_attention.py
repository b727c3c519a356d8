"""Decode-attention kernel layer (ops/decode_attention.py): numerical
parity of every swept KV-block candidate against the dense oracle (the
test_autotune.py pattern — the sweep optimizes time, never correctness),
the int8 quantization contract, the length-masking robustness the
length-aware grid rests on, and the autotune-table plumbing (CPU
defaults-only hermeticity included).

Kernels run in interpret mode on the CPU test backend — the numerics are
the kernel's own; only the timings need a chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_guide_tpu.ops import autotune
from distributed_tensorflow_guide_tpu.ops import decode_attention as DA

B, H, S, HD = 2, 3, 128, 16


@pytest.fixture(autouse=True)
def _isolated_table(isolated_autotune_table):
    yield


def _cache(seed=0, s=S):
    r = np.random.RandomState(seed)
    k = jnp.asarray(r.randn(B, H, s, HD), jnp.float32)
    v = jnp.asarray(r.randn(B, H, s, HD), jnp.float32)
    return k, v


def _q(c=1, seed=3):
    r = np.random.RandomState(seed)
    return jnp.asarray(r.randn(B, c, H, HD), jnp.float32)


def _dense_oracle(q, k, v, index, s=S):
    """The dense full-cache read the kernel must reproduce: same mask
    predicate, f32 softmax."""
    c = q.shape[1]
    scores = jnp.einsum("bqhd,bhkd->bhqk", q, k) / jnp.sqrt(HD)
    mask = jnp.arange(s)[None, :] <= (index + jnp.arange(c))[:, None]
    scores = jnp.where(mask[None, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1)
    return jnp.einsum("bhqk,bhkd->bqhd", probs, v)


# ---- numerical parity of the sweep space ------------------------------------


def test_every_swept_candidate_matches_dense_oracle():
    """Every (8, blk_k) candidate the decode sweep may ever pick must be
    numerically exact against the dense oracle — single-token decode at an
    early, a mid-cache and a full-cache index."""
    k, v = _cache()
    q = _q()
    cands = autotune.candidate_blocks(autotune.DECODE_KERNEL, s=S, d=HD,
                                      dtype=jnp.float32)
    assert cands and all(bq == autotune.DECODE_CHUNK_SUBLANES
                         for bq, _ in cands)
    for index in (0, 37, S - 1):
        ref = _dense_oracle(q, k, v, index)
        for _, bk in cands:
            got = DA.decode_attention(q, k, v, index, blk_k=bk)
            np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5,
                                       err_msg=f"blk_k {bk} index {index}")


def test_prefill_chunk_parity_and_padded_rows_sliced():
    """A multi-token chunk (prefill / speculative verify) through the same
    kernel: intra-chunk causality via the shared predicate, sublane-padded
    rows sliced off."""
    k, v = _cache(1)
    for c, index in ((5, 0), (4, 60), (9, 100)):
        q = _q(c)
        ref = _dense_oracle(q, k, v, index)
        got = DA.decode_attention(q, k, v, index, blk_k=64)
        assert got.shape == (B, c, H, HD)
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


def test_int8_parity_at_every_candidate():
    """Quantized kernel vs the dense oracle on the DEQUANTIZED cache: the
    fused dequant (scales folded into score and probability columns) must
    equal materialized dequantization exactly."""
    k, v = _cache(2)
    k8, ks = DA.quantize_kv(k)
    v8, vs = DA.quantize_kv(v)
    kd = k8.astype(jnp.float32) * ks[..., None]
    vd = v8.astype(jnp.float32) * vs[..., None]
    q = _q(seed=4)
    ref = _dense_oracle(q, kd, vd, 77)
    for _, bk in autotune.candidate_blocks(autotune.DECODE_KERNEL, s=S,
                                           d=HD, dtype=jnp.int8):
        got = DA.decode_attention(q, k8, v8, 77,
                                  key_scale=ks[:, :, None, :],
                                  value_scale=vs[:, :, None, :], blk_k=bk)
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5,
                                   err_msg=f"blk_k {bk}")


def test_garbage_beyond_length_cannot_leak():
    """The not-yet-written cache region is hidden by the mask AND skipped
    by the length-aware grid: poisoning every slot past the length with
    huge finite garbage (what stale slots actually hold — rejected
    speculative drafts, old sequences — is always finite) must not perturb
    a single output bit vs the zero-filled cache."""
    k, v = _cache(5)
    q = _q(seed=6)
    index = 41  # length 42: last live 64-block is [0, 64); [64, 128) dead
    poison = jnp.full_like(k, 1e6).at[:, :, :index + 1].set(
        k[:, :, :index + 1])
    vpoison = jnp.full_like(v, -1e6).at[:, :, :index + 1].set(
        v[:, :, :index + 1])
    want = DA.decode_attention(q, k, v, index, blk_k=64)
    got = DA.decode_attention(q, poison, vpoison, index, blk_k=64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---- quantization contract --------------------------------------------------


def test_quantize_kv_error_bound_and_zero_vector():
    r = np.random.RandomState(7)
    x = jnp.asarray(r.randn(4, 5, 64), jnp.float32) * 3.0
    q8, scale = DA.quantize_kv(x)
    assert q8.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    deq = q8.astype(jnp.float32) * scale[..., None]
    # symmetric round-to-nearest: error <= scale/2 per element
    assert np.all(np.abs(np.asarray(deq - x))
                  <= np.asarray(scale)[..., None] / 2 + 1e-7)
    z8, zscale = DA.quantize_kv(jnp.zeros((2, 3, 8)))
    np.testing.assert_array_equal(np.asarray(z8), 0)
    np.testing.assert_array_equal(np.asarray(zscale), 1.0)  # never 0/0


# ---- table plumbing ---------------------------------------------------------


def test_block_resolution_consults_table_and_survives_stale_entries():
    # seeded entry redirects the default resolution (cpu platform key —
    # only tests can seed it; the file path is closed by hermeticity)
    autotune._mem[autotune._key(autotune.DECODE_KERNEL, 0, 0, S, HD,
                                "int8", False, "cpu")] = {
        "blk_q": 8, "blk_k": 64}
    assert DA.decode_blk_k_for(b=B, h=H, s=S, d=HD, dtype=jnp.int8) == 64
    # a stale edge that no longer divides the cache is ignored
    autotune._mem[autotune._key(autotune.DECODE_KERNEL, 0, 0, S, HD,
                                "float32", False, "cpu")] = {
        "blk_q": 8, "blk_k": 96}
    blk = DA.decode_blk_k_for(b=B, h=H, s=S, d=HD, dtype=jnp.float32)
    assert S % blk == 0 and blk % 8 == 0
    # miss on an odd cache length falls down the divisor ladder
    assert DA.decode_blk_k_for(b=1, h=1, s=32, d=HD,
                               dtype=jnp.float32) == 32


def test_decode_sweep_mechanism_and_cpu_hermeticity():
    calls = []

    def measure(kern, blocks):
        calls.append(blocks)
        return 1.0 / blocks[1]  # favors the widest KV block

    best = autotune.ensure_tuned(autotune.DECODE_KERNEL, b=1, h=2, s=S,
                                 d=HD, dtype=jnp.int8, causal=False,
                                 measure=measure, platform="tpu")
    cands = autotune.candidate_blocks(autotune.DECODE_KERNEL, s=S, d=HD,
                                      dtype=jnp.int8)
    assert len(calls) == len(cands) and best == (8, max(
        bk for _, bk in cands))
    # no re-sweep on a hit; the generic entry serves other batch/heads
    again = autotune.ensure_tuned(autotune.DECODE_KERNEL, b=1, h=2, s=S,
                                  d=HD, dtype=jnp.int8, causal=False,
                                  measure=measure, platform="tpu")
    assert again == best and len(calls) == len(cands)
    assert DA.decode_blk_k_for(b=5, h=9, s=S, d=HD, dtype=jnp.int8,
                               platform="tpu") == best[1]
    # the CPU platform refuses to sweep (tier-1 defaults-only contract)
    with pytest.raises(RuntimeError, match="defaults-only"):
        DA.ensure_decode_tuned(b=1, h=2, s=S, d=HD, dtype=jnp.int8)


def test_runner_executes_and_matches_oracle():
    """The sweep/microbench runner drives the REAL kernel on a full cache;
    its int8 variant must agree with the dequantized oracle built from the
    same seeded operands."""
    fn = DA.make_decode_runner(64, b=1, h=2, s=64, d=16, dtype=jnp.int8)
    out = jax.block_until_ready(fn())
    assert out.shape == (1, 1, 2, 16)
    # rebuild the runner's operands (same seed path) for the oracle
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, 1, 2, 16), jnp.float32).astype(
        jnp.bfloat16)
    kf = jax.random.normal(keys[1], (1, 2, 64, 16), jnp.float32)
    vf = jax.random.normal(keys[2], (1, 2, 64, 16), jnp.float32)
    k8, ks = DA.quantize_kv(kf)
    v8, vs = DA.quantize_kv(vf)
    kd = k8.astype(jnp.float32) * ks[..., None]
    vd = v8.astype(jnp.float32) * vs[..., None]
    scores = jnp.einsum("bqhd,bhkd->bhqk", q.astype(jnp.float32), kd) \
        / jnp.sqrt(16.0)
    mask = jnp.arange(64)[None, :] <= jnp.asarray([63])[:, None]
    scores = jnp.where(mask[None, None], scores,
                       jnp.finfo(jnp.float32).min)
    ref = jnp.einsum("bhqk,bhkd->bqhd",
                     jax.nn.softmax(scores, -1), vd)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               atol=2e-2, rtol=2e-2)  # bf16 q + bf16 out
    f32fn = DA.make_decode_runner(64, b=1, h=2, s=64, d=16,
                                  dtype=jnp.float32)
    assert jax.block_until_ready(f32fn()).shape == (1, 1, 2, 16)


# ---- roofline byte model ----------------------------------------------------


def test_decode_kernel_hbm_bytes_closed_form():
    kw = dict(b=2, h=3, s=128, d=16)
    bf16 = DA.decode_kernel_hbm_bytes(dtype=jnp.bfloat16, **kw)
    i8 = DA.decode_kernel_hbm_bytes(dtype=jnp.int8, **kw)
    cache_elems = 2 * 2 * 3 * 128 * 16  # k and v
    qo = 2 * 2 * 3 * 1 * 16 * 2  # q + out, bf16
    assert bf16 == cache_elems * 2 + qo
    # int8 halves the cache term twice over bf16, plus the f32 scale rows
    assert i8 == cache_elems * 1 + 2 * 2 * 3 * 128 * 4 + qo
    # the length-aware model charges only live (block-rounded) slots
    short = DA.decode_kernel_hbm_bytes(dtype=jnp.bfloat16,
                                       effective_len=32, **kw)
    assert short == 2 * 2 * 3 * 32 * 16 * 2 + qo


def test_decode_flop_model_single_q_tile():
    """The decode grid has ONE fixed q tile — the FLOP model must charge
    s/blk_k KV blocks once, not the training kernels' (s/blk_q) x
    (s/blk_k) grid (which would inflate throughput ~s/blk_q-fold)."""
    got = autotune.kernel_flops(autotune.DECODE_KERNEL, b=2, h=3, s=1024,
                                d=64, blocks=(8, 256), causal=False)
    dp = autotune.padded_head_dim(64)
    assert got == 2.0 * 2 * 8 * 256 * dp * (1024 // 256) * 2 * 3
    # the flash forward at the same key is the full-grid count — strictly
    # larger (the bug this pins against)
    full = autotune.kernel_flops("flash_fwd", b=2, h=3, s=1024, d=64,
                                 blocks=(8, 256), causal=False)
    assert full == got * (1024 // 8)


def test_chunk_cap_routes_oversized_prefill_to_dense():
    """The q tile is unblocked, so chunks past DECODE_MAX_CHUNK are
    unsupported by design (VMEM) — supported() gates them out and
    decode_attention refuses them; _decode_attend routes them dense."""
    assert DA.supported(1024, 256, chunk=1)
    assert DA.supported(1024, 256, chunk=autotune.DECODE_MAX_CHUNK)
    assert not DA.supported(1024, 256, chunk=autotune.DECODE_MAX_CHUNK + 1)
    # an over-cap prefill chunk is refused outright (callers gate on
    # supported() first; max_len 256 so the chunk fits the cache)
    s2 = 256
    k2, v2 = _cache(8, s=s2)
    q_big = _q(c=autotune.DECODE_MAX_CHUNK + 1, seed=9)
    with pytest.raises(ValueError, match="chunk"):
        DA.decode_attention(q_big, k2, v2, 0, blk_k=64)


def test_vmem_model_and_candidates_valid():
    for s in (128, 256, 1024):
        cands = autotune.candidate_blocks(autotune.DECODE_KERNEL, s=s,
                                          d=64, dtype=jnp.int8)
        assert cands, s
        for bq, bk in cands:
            assert bq == autotune.DECODE_CHUNK_SUBLANES
            assert s % bk == 0 and bk % 8 == 0
            assert autotune.kernel_vmem_bytes(
                autotune.DECODE_KERNEL, bq, bk, 128,
                jnp.int8) <= autotune.VMEM_BUDGET_BYTES


# ---- paged pool variant (serve/) --------------------------------------------


def _paged(k, v, bs, *, ks=None, vs=None, seed=11):
    """Scatter dense (B, H, S, hd) caches into a SHUFFLED physical pool
    plus the block tables mapping them back — non-identity tables are the
    point: the kernel must resolve every tile through the indirection.
    The pool is in the one pool layout, (N, H, hd, bs): slots on lanes."""
    k, v = np.asarray(k), np.asarray(v)
    b, h, s, hd = k.shape
    n_blk = s // bs
    perm = np.random.RandomState(seed).permutation(b * n_blk)
    nb = b * n_blk + 1  # + the trash block convention
    kp = np.zeros((nb, h, hd, bs), k.dtype)
    vp = np.zeros((nb, h, hd, bs), v.dtype)
    ksp = np.ones((nb, h, 1, bs), np.float32)
    vsp = np.ones((nb, h, 1, bs), np.float32)
    tables = np.zeros((b, n_blk), np.int32)
    for bi in range(b):
        for j in range(n_blk):
            p = int(perm[bi * n_blk + j])
            sl = slice(j * bs, (j + 1) * bs)
            kp[p] = k[bi, :, sl].transpose(0, 2, 1)
            vp[p] = v[bi, :, sl].transpose(0, 2, 1)
            if ks is not None:
                ksp[p, :, 0] = np.asarray(ks)[bi, :, sl]
                vsp[p, :, 0] = np.asarray(vs)[bi, :, sl]
            tables[bi, j] = p
    out = (jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables))
    if ks is not None:
        out += (jnp.asarray(ksp), jnp.asarray(vsp))
    return out


@pytest.mark.parametrize("s,bs,blk_k", [(S, 32, 32), (512, 256, 128)])
def test_paged_kernel_matches_dense_oracle_through_shuffled_tables(s, bs,
                                                                   blk_k):
    """Single-token decode against the paged pool: per-request lengths,
    shuffled block tables, parity with the dense oracle on the contiguous
    view the tables encode — a tile the whole block, and two 128-lane
    tiles a block."""
    k, v = _cache(10, s=s)
    q = _q(seed=12)
    kp, vp, tables = _paged(k, v, bs)
    for lengths in ([s, s], [42, 97], [1, s]):
        got = DA.paged_decode_attention(
            q, kp, vp, tables, jnp.asarray(lengths, jnp.int32),
            block_size=bs, blk_k=blk_k)
        for bi, ln in enumerate(lengths):
            ref = _dense_oracle(q[bi:bi + 1], k[bi:bi + 1],
                                v[bi:bi + 1], ln - 1, s=s)
            np.testing.assert_allclose(
                got[bi:bi + 1], ref, atol=1e-5, rtol=1e-5,
                err_msg=f"req {bi} length {ln}")


def test_paged_chunk_parity():
    """A C>1 chunk (chunked prefill / the serve prefill program) through
    the paged kernel: request b's chunk occupies logical positions
    [lengths[b] - C, lengths[b]) with intra-chunk causality."""
    k, v = _cache(16)
    c = 4
    q = _q(c=c, seed=17)
    bs = 32
    kp, vp, tables = _paged(k, v, bs)
    lengths = [60, S]
    got = DA.paged_decode_attention(
        q, kp, vp, tables, jnp.asarray(lengths, jnp.int32),
        block_size=bs, blk_k=32)
    assert got.shape == (B, c, H, HD)
    for bi, ln in enumerate(lengths):
        ref = _dense_oracle(q[bi:bi + 1], k[bi:bi + 1], v[bi:bi + 1],
                            ln - c)
        np.testing.assert_allclose(got[bi:bi + 1], ref, atol=1e-5,
                                   rtol=1e-5, err_msg=f"req {bi}")


def test_paged_int8_parity():
    """Quantized pool (int8 blocks + f32 scale blocks in the pool's
    (N, H, 1, bs) layout) vs the dense oracle on the dequantized cache."""
    k, v = _cache(13)
    k8, ks = DA.quantize_kv(k)
    v8, vs = DA.quantize_kv(v)
    kd = k8.astype(jnp.float32) * ks[..., None]
    vd = v8.astype(jnp.float32) * vs[..., None]
    q = _q(seed=14)
    bs = 32
    k8p, v8p, tables, ksp, vsp = _paged(k8, v8, bs, ks=ks, vs=vs)
    lengths = [77, 33]
    got = DA.paged_decode_attention(
        q, k8p, v8p, tables, jnp.asarray(lengths, jnp.int32),
        key_scale_pool=ksp, value_scale_pool=vsp, block_size=bs,
        blk_k=32)
    for bi, ln in enumerate(lengths):
        ref = _dense_oracle(q[bi:bi + 1], kd[bi:bi + 1], vd[bi:bi + 1],
                            ln - 1)
        np.testing.assert_allclose(got[bi:bi + 1], ref, atol=1e-5,
                                   rtol=1e-5, err_msg=f"req {bi}")


def _random_pool(r, nb, kv, bs, cache):
    """A pool in its one layout, ``(nb, kv, HD, bs)``, at the cache's
    type; the int8 cache with its ``scale pool`` keywords."""
    kf = jnp.asarray(r.randn(nb, kv, HD, bs), jnp.float32)
    vf = jnp.asarray(r.randn(nb, kv, HD, bs), jnp.float32)
    if cache != "int8":
        dt = jnp.dtype(cache)
        return kf.astype(dt), vf.astype(dt), {}
    # quantize_kv scales a vector of hd values: the pool's axis 2
    kp, ks = DA.quantize_kv(jnp.swapaxes(kf, 2, 3))
    vp, vs = DA.quantize_kv(jnp.swapaxes(vf, 2, 3))
    return (jnp.swapaxes(kp, 2, 3), jnp.swapaxes(vp, 2, 3),
            dict(key_scale_pool=ks[:, :, None, :],  # (N, Hkv, 1, bs)
                 value_scale_pool=vs[:, :, None, :]))


def _gathered_dense(q, kp, vp, tables, lengths, *, key_scale_pool=None,
                    value_scale_pool=None):
    """The dense math on the views ``gather_view`` makes of a pool (what
    the fallback runs), a pool head beside each query head of its group;
    float32 throughout."""
    from distributed_tensorflow_guide_tpu.serve.paged_cache import (
        gather_view,
    )

    c, group = q.shape[1], q.shape[2] // kp.shape[1]

    def view(pool):  # (B, Hkv, d, S) -> a pool head beside each query head
        return jnp.repeat(gather_view(pool, tables)
                          .astype(jnp.float32), group, axis=1)

    keys, vals = view(kp), view(vp)
    if key_scale_pool is not None:
        keys, vals = keys * view(key_scale_pool), vals * view(
            value_scale_pool)
    scores = jnp.einsum("bqhd,bhdk->bhqk", q.astype(jnp.float32),
                        keys) / jnp.sqrt(q.shape[-1])
    q_pos = (lengths - c)[:, None] + jnp.arange(c)  # (B, C)
    mask = jnp.arange(keys.shape[-1])[None, None, :] <= q_pos[:, :, None]
    scores = jnp.where(mask[:, None], scores, jnp.finfo(jnp.float32).min)
    return jnp.einsum("bhqk,bhdk->bqhd", jax.nn.softmax(scores, -1), vals)


@pytest.mark.parametrize("group", [1, 3])
@pytest.mark.parametrize("cache", ["bf16", "int8"])
def test_paged_kernel_matches_gathered_dense_math(cache, group):
    """The kernel reading the pool as it is stored, (N, Hkv, hd, bs),
    against the dense math on the views ``gather_view`` makes of the same
    pool (what the fallback runs): a bfloat16 and an int8 pool, one pool
    head a query head and one under three, a 5-token chunk."""
    r = np.random.RandomState(21)
    bs, n_blk, c, kv = 32, 4, 5, H // group
    nb = B * n_blk + 1
    q = jnp.asarray(r.randn(B, c, H, HD), jnp.bfloat16)
    kp, vp, scales = _random_pool(
        r, nb, kv, bs, "bfloat16" if cache == "bf16" else cache)
    tables = jnp.asarray(r.permutation(nb - 1).reshape(B, n_blk), jnp.int32)
    lengths = jnp.asarray([77, 33], jnp.int32)
    got = DA.paged_decode_attention(q, kp, vp, tables, lengths,
                                    block_size=bs, **scales)
    assert got.shape == (B, c, H, HD) and got.dtype == jnp.bfloat16
    want = _gathered_dense(q, kp, vp, tables, lengths, **scales)
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2,
                               rtol=2e-2)


@pytest.mark.parametrize("heads_a_step", ["all", "one"])
@pytest.mark.parametrize("cache", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("chunk", [1, 5, 128])
@pytest.mark.parametrize("group", [1, 4])
def test_paged_kernel_all_heads_a_step_matches_dense(monkeypatch, group,
                                                     chunk, cache,
                                                     heads_a_step):
    """A grid step carries a key tile of every pool head (or, where the
    VMEM budget is too small, of a divisor of them) and the group's query
    heads ride on its sublanes, row ``r`` being head ``r // C`` at chunk
    position ``r % C``: against the dense read, over a decode step, a
    verify chunk and a prefill chunk, one query head a pool head and four,
    the model's cache types and int8, a shuffled table, and rows whose
    contexts are the chunk alone (one token at decode), end exactly on a
    tile's edge, and fill the view."""
    r = np.random.RandomState(31)
    rows, kv, bs, n_blk = 3, 2, 32, 8
    s, nb = n_blk * bs, rows * n_blk + 1
    q_dtype = jnp.float32 if cache == "float32" else jnp.bfloat16
    q = jnp.asarray(r.randn(rows, chunk, kv * group, HD), q_dtype)
    kp, vp, scales = _random_pool(r, nb, kv, bs, cache)
    tables = jnp.asarray(r.permutation(nb - 1).reshape(rows, n_blk),
                         jnp.int32)
    lengths = jnp.asarray([chunk, -(-(chunk + 1) // bs) * bs, s], jnp.int32)
    sizing = dict(group=group, chunk=chunk, hd=HD, blk_k=bs, dtype=kp.dtype,
                  q_dtype=q_dtype)
    assert DA.paged_heads_per_step(kv, **sizing) == kv
    if heads_a_step == "one":
        # half of the least budget that holds both heads holds one: the
        # derived count is a divisor of the heads (of three heads one, of
        # four two, under the budget two fit), and the grid walks the rest
        both = next(b for b in range(1 << 12, 1 << 24, 1 << 12)
                    if DA.paged_heads_per_step(kv, budget=b, **sizing) == kv)
        assert DA.paged_heads_per_step(3, budget=both, **sizing) == 1
        assert DA.paged_heads_per_step(4, budget=both, **sizing) == 2
        assert DA.paged_heads_per_step(kv, budget=both // 2, **sizing) == 1
        assert DA.paged_heads_per_step(kv, budget=1, **sizing) == 1
        monkeypatch.setattr(autotune, "VMEM_BUDGET_BYTES", both // 2)
    got = DA.paged_decode_attention(q, kp, vp, tables, lengths,
                                    block_size=bs, blk_k=bs, **scales)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = _gathered_dense(q, kp, vp, tables, lengths, **scales)
    tol = 1e-5 if cache == "float32" else 2e-2
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=tol,
                               rtol=tol)


def test_paged_dead_blocks_cannot_leak():
    """Pool contents past a request's length — whole dead blocks AND the
    dead tail of its last partially-live block (what freed/stale blocks
    actually hold) — must not perturb one output bit."""
    k, v = _cache(14)
    q = _q(seed=15)
    bs = 32
    kp, vp, tables = _paged(k, v, bs)
    lengths = jnp.asarray([42, 10], jnp.int32)
    want = DA.paged_decode_attention(q, kp, vp, tables, lengths,
                                     block_size=bs, blk_k=32)
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for bi in range(B):
        ln = int(lengths[bi])
        for j in range(tables.shape[1]):
            p = int(tables[bi, j])
            if j * bs >= ln:  # fully dead block
                kp2[p], vp2[p] = 1e6, -1e6
            elif (j + 1) * bs > ln:  # partially live: poison the tail
                kp2[p, :, :, ln - j * bs:] = 1e6
                vp2[p, :, :, ln - j * bs:] = -1e6
    got = DA.paged_decode_attention(q, jnp.asarray(kp2),
                                    jnp.asarray(vp2), tables, lengths,
                                    block_size=bs, blk_k=32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_blk_k_resolution_and_supported():
    def tuned(blk_k):
        autotune._mem[autotune._key(autotune.PAGED_DECODE_KERNEL, 0, 0, 512,
                                    HD, "float32", False, "cpu")] = {
            "blk_q": 8, "blk_k": blk_k}

    def resolve(block_size):
        return DA.paged_decode_blk_k_for(b=B, h=H, s=512, d=HD,
                                         dtype=jnp.float32,
                                         block_size=block_size)

    # a tuned edge that tiles the pool block is honored: it divides the
    # block, and a tile smaller than a block is whole 128-lane groups
    # (the pool keeps a block's slots on the lane axis)
    tuned(128)
    assert resolve(256) == 128
    # one that would straddle physical blocks is ignored, and so is one
    # that is no lane extent: the block itself is the tile then
    tuned(64)
    assert resolve(32) == 32
    tuned(16)
    assert resolve(32) == 32
    assert resolve(512) == 256  # ... or the cascade's largest that tiles it
    assert DA.paged_supported(S, 32, 32)
    assert DA.paged_supported(512, 256, 128)
    assert not DA.paged_supported(S, 32, 16)  # 16 lanes are no tile
    assert not DA.paged_supported(S, 32, 64)  # tile straddles blocks
    assert not DA.paged_supported(120, 32, 32)  # ragged final block
    assert not DA.paged_supported(S, 32, 32,
                                  chunk=autotune.DECODE_MAX_CHUNK + 1)
    # a straddling blk_k is refused outright at call time
    with pytest.raises(ValueError, match="unsupported"):
        DA.paged_decode_attention(
            _q(seed=19), jnp.zeros((9, H, HD, 32)),
            jnp.zeros((9, H, HD, 32)),
            jnp.zeros((B, 4), jnp.int32), jnp.asarray([1, 1]),
            block_size=32, blk_k=64)


def test_paged_sweep_skips_straddling_candidates_and_cpu_refusal():
    # the CPU platform refuses to sweep (tier-1 defaults-only contract,
    # same as the contiguous decode sweep)
    with pytest.raises(RuntimeError, match="defaults-only"):
        DA.ensure_paged_decode_tuned(b=1, h=1, s=S, d=16,
                                     dtype=jnp.float32, block_size=64)
    # under the tpu key the sweep runs; the blk_k=128 candidate straddles
    # the 64-slot block and must be skipped as failed, not crash the row
    best = DA.ensure_paged_decode_tuned(b=1, h=1, s=S, d=16,
                                        dtype=jnp.float32, block_size=64,
                                        iters=1, platform="tpu")
    assert best == 64
    entry = autotune._mem[autotune._key(
        autotune.PAGED_DECODE_KERNEL, 0, 0, S, 16, "float32", False,
        "tpu")]
    skipped = {f["blk_k"] for f in entry["detail"]["failed"]}
    assert skipped == {128}
    # resolution now serves the recorded edge for the same shape
    assert DA.paged_decode_blk_k_for(b=1, h=1, s=S, d=16,
                                     dtype=jnp.float32, block_size=64,
                                     platform="tpu") == best


def test_paged_runner_executes_and_matches_oracle():
    """The paged sweep/microbench unit drives the REAL kernel on a full
    identity-table pool; its output must match the dense oracle built
    from the same seeded operands."""
    fn = DA.make_paged_decode_runner(16, b=1, h=2, s=64, d=16,
                                     dtype=jnp.float32, block_size=16)
    out = jax.block_until_ready(fn())
    assert out.shape == (1, 1, 2, 16)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, 1, 2, 16), jnp.float32)
    # the runner draws (N, H, bs, hd) and hands the kernel its transpose
    kf = jax.random.normal(keys[1], (5, 2, 16, 16), jnp.float32)
    vf = jax.random.normal(keys[2], (5, 2, 16, 16), jnp.float32)
    kd = jnp.concatenate([kf[j] for j in range(4)], axis=1)[None]
    vd = jnp.concatenate([vf[j] for j in range(4)], axis=1)[None]
    scores = jnp.einsum("bqhd,bhkd->bhqk", q, kd) / jnp.sqrt(16.0)
    ref = jnp.einsum("bhqk,bhkd->bqhd", jax.nn.softmax(scores, -1), vd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    # given fewer pool heads than query heads, the runner draws the pool
    # at that count and both query heads read its one head
    shared = DA.make_paged_decode_runner(16, b=1, h=2, s=64, d=16,
                                         dtype=jnp.float32, block_size=16,
                                         kv_heads=1)()
    one = jax.random.normal(keys[1], (5, 1, 16, 16), jnp.float32)
    kd1 = jnp.concatenate([one[j] for j in range(4)], axis=1)[None]
    vd1 = jnp.concatenate(
        [jax.random.normal(keys[2], (5, 1, 16, 16), jnp.float32)[j]
         for j in range(4)], axis=1)[None]
    scores = jnp.einsum("bqhd,bkd->bhqk", q, kd1[:, 0]) / jnp.sqrt(16.0)
    ref1 = jnp.einsum("bhqk,bkd->bqhd", jax.nn.softmax(scores, -1),
                      vd1[:, 0])
    np.testing.assert_allclose(np.asarray(shared), np.asarray(ref1),
                               atol=1e-5, rtol=1e-5)
