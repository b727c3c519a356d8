"""Operations and bytes the algorithms need, from shapes alone.

These are the numerators of every share of a peak or of a roofline the
benchmark reports. They count what the mathematics needs and nothing the
implementation adds: no recomputation, no padding rows, no empty slots, no
keys past a causal mask or past a sequence's live length. So a share built
on them cannot pass 100% unless the time under it leaves work out.

Copied in spirit from ``benchmarks/common.py`` (``lm_model_flops_per_step``:
backward is twice forward) and ``ops/decode_attention.py``
(``decode_kernel_hbm_bytes``: the live slice of the cache read once), closed
forms instead of a traced program so that no later PR can move them.
"""

from __future__ import annotations


def block_matmul_flops_per_token(d: int, ff: int) -> int:
    """qkv (d x 3d), proj (d x d), up (d x ff), down (ff x d): two
    operations a multiply-add."""
    return 2 * (3 * d * d + d * d + 2 * d * ff)


def attention_flops(d: int, keys: int) -> int:
    """One query position of one layer attending ``keys`` keys over all
    heads: scores and the weighted sum, ``d = heads * head_dim``."""
    return 2 * 2 * d * keys


def causal_keys(positions: int) -> int:
    """Keys attended by ``positions`` consecutive queries from position 0
    under a causal mask: 1 + 2 + ... + positions."""
    return positions * (positions + 1) // 2


def lm_forward_flops(*, d: int, ff: int, layers: int, vocab: int,
                     positions: int, head_rows: int) -> int:
    """One sequence's forward pass over ``positions`` tokens from position
    0, with the output head applied to ``head_rows`` of them."""
    trunk = layers * (positions * block_matmul_flops_per_token(d, ff)
                      + attention_flops(d, causal_keys(positions)))
    return trunk + head_rows * 2 * d * vocab


def lm_train_step_flops(*, d: int, ff: int, layers: int, vocab: int,
                        batch: int, seq: int) -> int:
    """Forward and backward of a next-token loss over ``batch`` rows of
    ``seq``: the last position predicts nothing, so ``seq - 1`` positions
    carry the loss; backward is twice forward (PaLM, appendix B)."""
    fwd = lm_forward_flops(d=d, ff=ff, layers=layers, vocab=vocab,
                           positions=seq - 1, head_rows=seq - 1)
    return 3 * batch * fwd


def lm_token_flops(*, d: int, ff: int, layers: int, position: int) -> int:
    """The trunk's forward for one token at ``position`` (0-based) that
    attends ``position + 1`` keys through a cache."""
    return layers * (block_matmul_flops_per_token(d, ff)
                     + attention_flops(d, position + 1))


def lm_span_flops(*, d: int, ff: int, layers: int, start: int,
                  stop: int) -> int:
    """The trunk's forward for the tokens at positions ``[start, stop)``."""
    n = stop - start
    keys = causal_keys(stop) - causal_keys(start)
    return layers * (n * block_matmul_flops_per_token(d, ff)
                     + attention_flops(d, keys))


def head_flops(*, d: int, vocab: int, rows: int = 1) -> int:
    return rows * 2 * d * vocab


# ---- kernels ----------------------------------------------------------


def flash_forward(*, batch: int, heads: int, seq: int, head_dim: int,
                  itemsize: int = 2) -> tuple[int, int]:
    """Causal attention forward for (batch, seq, heads, head_dim): the
    operations under the mask, and q, k, v read once and o written once
    (the row statistics for the backward, float32, written once)."""
    flops = batch * attention_flops(heads * head_dim, causal_keys(seq))
    qkvo = 4 * batch * heads * seq * head_dim * itemsize
    stats = batch * heads * seq * 4
    return flops, qkvo + stats


def flash_backward_dq(*, batch: int, heads: int, seq: int, head_dim: int,
                      itemsize: int = 2) -> tuple[int, int]:
    """dq: scores again, dp = do v^T, dq = ds k: three products under the
    mask; reads q, k, v, do and the statistics, writes dq."""
    flops = batch * 3 * 2 * heads * head_dim * causal_keys(seq)
    return flops, (5 * batch * heads * seq * head_dim * itemsize
                   + 2 * batch * heads * seq * 4)


def flash_backward_dkv(*, batch: int, heads: int, seq: int, head_dim: int,
                       itemsize: int = 2) -> tuple[int, int]:
    """dk and dv: scores again, dv = p^T do, dp = do v^T, dk = ds^T q:
    four products under the mask; reads q, k, v, do and the statistics,
    writes dk and dv."""
    flops = batch * 4 * 2 * heads * head_dim * causal_keys(seq)
    return flops, (6 * batch * heads * seq * head_dim * itemsize
                   + 2 * batch * heads * seq * 4)


def paged_decode(*, live_keys: int, rows: int, heads: int, head_dim: int,
                 cache_itemsize: int = 2,
                 io_itemsize: int = 2) -> tuple[int, int]:
    """One layer's decode attention for ``rows`` one-token queries whose
    sequences hold ``live_keys`` keys in all (exact lengths, not rounded up
    to blocks): keys and values read once, q read and o written once."""
    d = heads * head_dim
    flops = attention_flops(d, live_keys)
    kv = 2 * live_keys * d * cache_itemsize
    return flops, kv + 2 * rows * d * io_itemsize


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak operations a second and bytes over peak bytes a second."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
