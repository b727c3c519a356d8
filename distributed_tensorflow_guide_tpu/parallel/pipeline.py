"""Pipeline parallelism — judged config 5: "GPT-2 124M pipeline-parallel
across a v5e-16 pod slice" (BASELINE.json).

No pipeline exists in the reference (SURVEY.md §2c). Design: GPipe microbatch
schedule (Huang et al. 2019) expressed as ONE compiled SPMD program — the
pipeline "stages" are not processes (the reference's only composition
mechanism) but shards of a stacked-layer parameter tree over the ``pipe``
mesh axis, and the stage-to-stage hand-off is a single ICI-neighbor
``lax.ppermute`` per tick inside a ``lax.scan``:

    tick t:  stage 0 injects microbatch t | stage s runs layers on the
             activation it received at t-1 | everyone ppermutes output to s+1

    M microbatches, P stages → M+P-1 ticks; bubble fraction (P-1)/(M+P-1).

Differentiating *through* the scan+ppermute gives the backward pipeline for
free (ppermute's transpose is the reverse ppermute) — no hand-written
backward schedule, no send/recv pairs to keep in sync.

Embedding params live logically on stage 0 and head params on stage P-1:
every stage holds a copy, but only the owning stage's compute reaches the
loss, so the others' grads are structurally zero and one ``psum`` over
``pipe`` reconstitutes the true gradient. Composes with data parallelism
(``data`` axis pmean) in the same shard_map.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import distributed_tensorflow_guide_tpu.collectives as cc
from distributed_tensorflow_guide_tpu.core.mesh import axis_sizes
from distributed_tensorflow_guide_tpu.utils.spec_utils import expand_prefix
from distributed_tensorflow_guide_tpu.models.transformer import (
    Block,
    TransformerConfig,
)


def _freeze_tables(fn):
    """Cache a schedule generator on its (M, P, v) key and mark the numpy
    tables read-only. The generators are trace-time Python (greedy
    simulations, O(T*P)); at judged scale (P=16, M=64, v=2) regenerating
    them on every retrace — new microbatch count, new donate configuration,
    eval vs train variant — is pure waste, and the cache makes a retrace's
    schedule cost one dict lookup. Freezing makes sharing safe: a caller
    mutating a cached table would silently corrupt every later trace."""

    @functools.lru_cache(maxsize=64)
    def cached(*key):
        out = fn(*key)
        for v_ in out.values():
            if hasattr(v_, "flags"):
                v_.flags.writeable = False
        return out

    return functools.wraps(fn)(cached)


@_freeze_tables
def _make_1f1b_schedule(M: int, P: int):
    """Static 1F1B schedule (Narayanan et al. 2019, PipeDream-flush).

    Returns numpy tables driving the SPMD tick loop:
      op[t, s] in {0 idle, 1 forward, 2 backward}; mb[t, s] = microbatch.
      sa/sam[t, s]: stage s must store the activation that arrived this tick
        (sent by s-1 at t-1) into slot ``sam % R``; sc/scm likewise for
        cotangents from s+1.
      R: ring-buffer depth (max in-flight microbatches + safety check that no
        slot is overwritten before consumption).
      T: total ticks.

    Greedy simulation: each stage forwards through its warmup window
    (min(P-s, M) microbatches), then strictly alternates backward-preferred /
    forward — the classic 1F1B steady state that caps in-flight activations
    at ~P-s instead of GPipe's M.
    """
    import numpy as np

    next_f = [0] * P
    next_b = [0] * P
    f_tick = [[-1] * M for _ in range(P)]
    b_tick = [[-1] * M for _ in range(P)]
    op_rows: list[list[int]] = []
    mb_rows: list[list[int]] = []
    t = 0
    max_inflight = 1
    while any(next_b[s] < M for s in range(P)):
        row_op = [0] * P
        row_mb = [0] * P
        for s in range(P):
            cap = min(P - s, M)  # 1F1B in-flight bound for stage s
            can_f = (
                next_f[s] < M
                and next_f[s] - next_b[s] < cap
                and (s == 0 or 0 <= f_tick[s - 1][next_f[s]] < t)
            )
            can_b = next_b[s] < next_f[s] and (
                s == P - 1 or 0 <= b_tick[s + 1][next_b[s]] < t
            )
            if s == P - 1 and can_b and not (0 <= f_tick[s][next_b[s]] < t):
                can_b = False
            in_warmup = next_f[s] < cap
            if can_f and in_warmup:
                row_op[s], row_mb[s] = 1, next_f[s]
            elif can_b:
                row_op[s], row_mb[s] = 2, next_b[s]
            elif can_f:
                row_op[s], row_mb[s] = 1, next_f[s]
        for s in range(P):
            if row_op[s] == 1:
                f_tick[s][row_mb[s]] = t
                next_f[s] += 1
            elif row_op[s] == 2:
                b_tick[s][row_mb[s]] = t
                next_b[s] += 1
            max_inflight = max(max_inflight, next_f[s] - next_b[s])
        op_rows.append(row_op)
        mb_rows.append(row_mb)
        t += 1
        if t > 6 * (M + P) + 16:
            raise RuntimeError("1F1B schedule generation did not converge")
    T = t
    op = np.array(op_rows, np.int32)
    mb = np.array(mb_rows, np.int32)

    # receive bookkeeping: arrival at tick t is what the neighbor sent at t-1
    sa = np.zeros((T, P), np.int32)
    sam = np.zeros((T, P), np.int32)
    sc = np.zeros((T, P), np.int32)
    scm = np.zeros((T, P), np.int32)
    for tt in range(1, T):
        for s in range(P):
            if s > 0 and op[tt - 1, s - 1] == 1:
                sa[tt, s], sam[tt, s] = 1, mb[tt - 1, s - 1]
            if s < P - 1 and op[tt - 1, s + 1] == 2:
                sc[tt, s], scm[tt, s] = 1, mb[tt - 1, s + 1]

    def slots_ok(R: int) -> bool:
        """No buffer slot may be overwritten before its consumer runs."""
        for s in range(P):
            # act_buf: arrival (t from sa) .. consumption (F at stage s);
            # resid:   store (F) .. consumption (B); cot_buf: arrival .. B.
            intervals: dict[int, list[tuple[int, int]]] = {}

            def add(slot, t0, t1):
                intervals.setdefault(slot, []).append((t0, t1))

            for m in range(M):
                if s > 0:
                    add(m % R, f_tick[s - 1][m] + 1, f_tick[s][m])
                add((m % R) + R, f_tick[s][m], b_tick[s][m])  # resid
                if s < P - 1:
                    add((m % R) + 2 * R, b_tick[s + 1][m] + 1, b_tick[s][m])
            for spans in intervals.values():
                spans.sort()
                for (a0, a1), (b0, _b1) in zip(spans, spans[1:]):
                    if b0 <= a1:
                        return False
        return True

    R = max_inflight
    while not slots_ok(R):  # pragma: no cover - safety margin
        R += 1
    return {"op": op, "mb": mb, "sa": sa, "sam": sam, "sc": sc, "scm": scm,
            "R": R, "T": T}


@_freeze_tables
def _make_interleaved_1f1b_schedule(M: int, P: int, v: int):
    """Static interleaved-1F1B schedule (Megatron-LM's combined schedule:
    Narayanan et al. 2021 §2.2) — BOTH the 1F1B O(P) in-flight memory cap
    and interleaving's ~v-fold bubble shrink in one table.

    D = v*P chunk-stages; chunk-stage k = j*P + s runs on device s as local
    chunk row j, so every k -> k+1 hand-off is one forward ``ppermute`` hop
    and every cotangent hand-off one backward hop. Each device executes a
    FIXED op sequence (warmup forwards, then 1F/1B pairs, then cooldown
    backwards), stalling when an op's input has not yet arrived — exactly
    how Megatron's executor behaves, here pre-simulated into per-tick
    tables so the SPMD loop stays a static ``lax.scan``.

    Per-device op order (requires ``M % P == 0``, as in Megatron):
      - forwards are chunk-grouped: chunk 0 takes microbatches 0..P-1, then
        chunk 1 takes 0..P-1, ... chunk v-1, then chunk 0 takes P..2P-1, …
      - backwards mirror it with chunks reversed (v-1 first).
      - warmup length W(s) = min(v*M, 2*(P-s-1) + (v-1)*P).

    Returns tables (T, P): ``op`` (0 idle / 1 fwd / 2 bwd), ``jr`` (local
    chunk row), ``mb``; arrival tables ``sa``/``saj``/``sam`` (an
    activation sent by device s-1 at t-1 lands this tick, destined for
    local chunk row ``saj``, microbatch ``sam``) and ``sc``/``scj``/``scm``
    for cotangents; ring depth ``R`` (slot = (j*M + m) % R, interval-
    checked); ``f_done``/``b_done`` tick stamps; ``T``.
    """
    import numpy as np

    if P < 2 or v < 2:
        raise ValueError(f"interleaved 1F1B needs P >= 2, v >= 2 (got {P}, {v})")
    if M % P:
        raise ValueError(
            f"interleaved 1F1B requires num_microbatches % pipe == 0 "
            f"(got M={M}, P={P}) — the chunk-grouped issue order rides "
            "groups of P microbatches"
        )
    D = v * P
    TF = v * M  # forward (and backward) ops per device

    def f_index(i):
        group, pos = divmod(i, P)
        rnd, j = divmod(group, v)
        return j, rnd * P + pos

    def b_index(i):
        group, pos = divmod(i, P)
        rnd, jr = divmod(group, v)
        return v - 1 - jr, rnd * P + pos

    seqs: list[list[tuple[int, int, int]]] = []
    for s in range(P):
        W = min(TF, 2 * (P - s - 1) + (v - 1) * P)
        ops: list[tuple[int, int, int]] = []
        nf = nb = 0
        while nf < W:
            ops.append((1, *f_index(nf)))
            nf += 1
        while nf < TF:
            ops.append((1, *f_index(nf)))
            nf += 1
            ops.append((2, *b_index(nb)))
            nb += 1
        while nb < TF:
            ops.append((2, *b_index(nb)))
            nb += 1
        seqs.append(ops)

    ptr = [0] * P
    f_done = [[-1] * M for _ in range(D)]
    b_done = [[-1] * M for _ in range(D)]
    rows: list[list[tuple[int, int, int]]] = []
    t = 0
    while any(ptr[s] < 2 * TF for s in range(P)):
        row: list[tuple[int, int, int]] = []
        for s in range(P):
            if ptr[s] >= 2 * TF:
                row.append((0, 0, 0))
                continue
            op, j, m = seqs[s][ptr[s]]
            k = j * P + s
            if op == 1:
                ready = k == 0 or 0 <= f_done[k - 1][m] < t
            else:
                ready = 0 <= f_done[k][m] < t and (
                    k == D - 1 or 0 <= b_done[k + 1][m] < t
                )
            row.append((op, j, m) if ready else (0, 0, 0))
        progress = False
        for s, (op, j, m) in enumerate(row):
            if op == 1:
                f_done[j * P + s][m] = t
                ptr[s] += 1
                progress = True
            elif op == 2:
                b_done[j * P + s][m] = t
                ptr[s] += 1
                progress = True
        if not progress:  # pragma: no cover - the fixed order is deadlock-free
            raise RuntimeError(
                f"interleaved 1F1B schedule deadlocked at tick {t} "
                f"(M={M}, P={P}, v={v})"
            )
        rows.append(row)
        t += 1
        if t > 8 * (TF + P) + 16:  # pragma: no cover - safety
            raise RuntimeError("interleaved 1F1B schedule did not converge")
    T = t

    op = np.zeros((T, P), np.int32)
    jr = np.zeros((T, P), np.int32)
    mb = np.zeros((T, P), np.int32)
    for tt, row in enumerate(rows):
        for s, (o, j, m) in enumerate(row):
            op[tt, s], jr[tt, s], mb[tt, s] = o, j, m

    # Arrivals: what device s-1 forwarded at t-1 lands on s at t (destined
    # for chunk-stage k+1, unless k was the tap D-1); what device s+1
    # backwarded at t-1 lands on s as the cotangent for chunk-stage k-1.
    sa = np.zeros((T, P), np.int32)
    saj = np.zeros((T, P), np.int32)
    sam = np.zeros((T, P), np.int32)
    sc = np.zeros((T, P), np.int32)
    scj = np.zeros((T, P), np.int32)
    scm = np.zeros((T, P), np.int32)
    for tt in range(1, T):
        for s in range(P):
            o, j, m = rows[tt - 1][(s - 1) % P]
            if o == 1:
                k = j * P + (s - 1) % P
                if k + 1 < D:
                    assert (k + 1) % P == s
                    sa[tt, s], saj[tt, s], sam[tt, s] = 1, (k + 1) // P, m
            o, j, m = rows[tt - 1][(s + 1) % P]
            if o == 2:
                k = j * P + (s + 1) % P
                if k - 1 >= 0:
                    assert (k - 1) % P == s
                    sc[tt, s], scj[tt, s], scm[tt, s] = 1, (k - 1) // P, m

    def slots_ok(R: int) -> bool:
        """No (j*M+m) % R slot overwritten before its consumer runs."""
        for s in range(P):
            intervals: dict[int, list[tuple[int, int]]] = {}

            def add(slot, t0, t1):
                intervals.setdefault(slot, []).append((t0, t1))

            for j in range(v):
                k = j * P + s
                for m in range(M):
                    u = (j * M + m) % R
                    if k > 0:
                        add(u, f_done[k - 1][m] + 1, f_done[k][m])
                    add(u + R, f_done[k][m], b_done[k][m])  # resid
                    if k < D - 1:
                        add(u + 2 * R, b_done[k + 1][m] + 1, b_done[k][m])
            for spans in intervals.values():
                spans.sort()
                for (a0, a1), (b0, _b1) in zip(spans, spans[1:]):
                    if b0 <= a1:
                        return False
        return True

    max_inflight = 1
    for s in range(P):
        events = []
        for j in range(v):
            k = j * P + s
            for m in range(M):
                events.append((f_done[k][m], 1))
                events.append((b_done[k][m], -1))
        cur = 0
        for _, d in sorted(events):
            cur += d
            max_inflight = max(max_inflight, cur)
    R = max_inflight
    while not slots_ok(R):
        R += 1
    return {"op": op, "jr": jr, "mb": mb, "sa": sa, "saj": saj, "sam": sam,
            "sc": sc, "scj": scj, "scm": scm, "R": R, "T": T,
            "f_done": f_done, "b_done": b_done,
            "max_inflight": max_inflight}


@_freeze_tables
def _make_interleaved_schedule(M: int, P: int, v: int):
    """Forward schedule for interleaved GPipe (Megatron virtual stages):
    D = v*P chunk-stages laid round-robin on P devices (chunk-stage k lives
    on device k % P as local chunk row j = k // P). One op per device per
    tick; drain priority (deepest ready chunk first); chunk-stage k of
    microbatch m runs strictly after k-1 of m. Shrinks the pipeline bubble
    from (P-1)/(M+P-1) toward (P-1)/(vM+P-1): each fill/drain slot costs a
    1/v-stage chunk instead of a full stage.

    Returns numpy tables (T, P): ``jrow``/``mbrow`` (op, -1 = idle),
    ``rflag``/``rj``/``rm`` (landing slot for the activation that arrives
    this tick), plus ``done[k][m]`` tick stamps and ``T``.
    """
    import numpy as np

    D = v * P
    done = [[-1] * M for _ in range(D)]
    nxt = [0] * D
    t = 0
    ops: list[list[tuple[int, int]]] = []
    while any(nxt[k] < M for k in range(D)):
        row = [(-1, -1)] * P
        for s in range(P):
            for j in reversed(range(v)):
                k = j * P + s
                m = nxt[k]
                if m >= M:
                    continue
                if k == 0 or 0 <= done[k - 1][m] < t:
                    row[s] = (j, m)
                    break
        for s in range(P):
            j, m = row[s]
            if j >= 0:
                done[j * P + s][m] = t
                nxt[j * P + s] += 1
        ops.append(row)
        t += 1
        if t > 10 * (M * v + P) + 16:  # pragma: no cover - safety
            raise RuntimeError("interleaved schedule did not converge")
    T = t
    jrow = np.full((T, P), -1, np.int32)
    mbrow = np.zeros((T, P), np.int32)
    for tt, row in enumerate(ops):
        for s in range(P):
            j, m = row[s]
            jrow[tt, s] = j
            mbrow[tt, s] = m if j >= 0 else 0
    # Arrivals: what device s-1 (mod P) ran at t-1 lands on s at t, destined
    # for chunk-stage k+1 = same local row j (or j+1 when wrapping P-1 -> 0).
    # The last chunk-stage's output never lands anywhere (it is the tap).
    rflag = np.zeros((T, P), np.int32)
    rj = np.zeros((T, P), np.int32)
    rm = np.zeros((T, P), np.int32)
    for tt in range(1, T):
        for s in range(P):
            sp = (s - 1) % P
            j, m = ops[tt - 1][sp]
            if j < 0:
                continue
            k_next = j * P + sp + 1
            if k_next >= D:
                continue  # tap, not a hand-off
            assert k_next % P == s
            rflag[tt, s] = 1
            rj[tt, s] = k_next // P
            rm[tt, s] = m
    return {"jrow": jrow, "mbrow": mbrow, "rflag": rflag, "rj": rj,
            "rm": rm, "done": done, "T": T}


class _Embedder(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                     name="tok_emb")(tokens)
        pos = nn.Embed(cfg.max_len, cfg.d_model, dtype=cfg.dtype,
                       name="pos_emb")(jnp.arange(tokens.shape[1])[None, :])
        return x + pos


class _Head(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(dtype=self.cfg.dtype, name="ln_f")(x)
        return nn.Dense(self.cfg.vocab_size, dtype=jnp.float32, use_bias=False,
                        name="lm_head")(x)


class PipelinedLM:
    """GPipe LM training over the ``pipe`` (× ``data``) mesh axes."""

    def __init__(self, mesh: Mesh, cfg: TransformerConfig,
                 num_microbatches: int, schedule: str = "gpipe",
                 virtual_chunks: int = 1, fused_ce="auto",
                 ce_chunk: int | None = None, precision=None):
        if schedule not in ("auto", "gpipe", "1f1b"):
            raise ValueError(f"unknown pipeline schedule {schedule!r}")
        if precision is not None:
            # core/precision.py policy: one object sets activation dtype +
            # the selective-remat mode instead of per-call-site dtypes
            from distributed_tensorflow_guide_tpu.core import (
                precision as precision_mod,
            )

            cfg = precision_mod.resolve(precision).apply_to_transformer(cfg)
        sizes = axis_sizes(mesh)
        if schedule == "auto":
            # Measured policy (round-5 on-chip battery): at pipe=1 the 1F1B
            # manual-VJP machinery is pure overhead — GPipe 99,737 vs 1F1B
            # 87,901 tok/s at the judged shape (~12%); at pipe>=2 the 1F1B
            # O(P) in-flight activation cap is what pipelining is for.
            schedule = "gpipe" if sizes["pipe"] == 1 else "1f1b"
        elif schedule == "1f1b" and sizes["pipe"] == 1:
            import logging

            logging.getLogger("dtg.parallel.pipeline").warning(
                "schedule='1f1b' on a single-stage mesh (pipe=1): the "
                "manual-VJP tick machinery is pure overhead with no "
                "in-flight activations to cap (round-5 battery: GPipe "
                "99,737 vs 1F1B 87,901 tok/s). schedule='auto' picks "
                "GPipe here.")
        self.mesh = mesh
        self.cfg = cfg
        self.schedule = schedule
        self.n_stages = sizes["pipe"]
        self.n_data = sizes["data"]
        self.num_microbatches = num_microbatches
        # Interleaved schedules (Megatron virtual stages): each device holds
        # ``virtual_chunks`` non-contiguous layer chunks; chunk-stage
        # k = j*P + s lives on device s as local row j. Fill/drain slots
        # cost a 1/v stage, shrinking the bubble ~v-fold. Under gpipe the
        # autodiff produces the reversed drain (_make_interleaved_schedule);
        # under 1f1b the combined Megatron schedule
        # (_make_interleaved_1f1b_schedule) ALSO keeps the O(P) in-flight
        # memory cap — the production pairing.
        if virtual_chunks < 1:
            raise ValueError(f"virtual_chunks must be >= 1, got {virtual_chunks}")
        if virtual_chunks > 1 and schedule == "1f1b":
            if sizes["pipe"] < 2:
                raise ValueError(
                    "interleaved 1F1B needs pipe >= 2 (got "
                    f"{sizes['pipe']}); gpipe handles the degenerate case"
                )
            if num_microbatches % sizes["pipe"]:
                raise ValueError(
                    f"interleaved 1F1B requires num_microbatches divisible "
                    f"by pipe ({num_microbatches} % {sizes['pipe']} != 0)"
                )
        self.virtual_chunks = virtual_chunks
        n_chunk_stages = self.n_stages * virtual_chunks
        if cfg.num_layers % n_chunk_stages:
            raise ValueError(
                f"{cfg.num_layers} layers not divisible by "
                f"{n_chunk_stages} chunk-stages "
                f"({self.n_stages} stages x {virtual_chunks} chunks)"
            )
        self.layers_per_stage = cfg.num_layers // self.n_stages
        self.layers_per_chunk = cfg.num_layers // n_chunk_stages
        self.embedder = _Embedder(cfg)
        self.head = _Head(cfg)
        self.block = Block(cfg)
        # Chunked fused cross-entropy (ops/fused_ce.py): the loss and its
        # grad-of-logits run per vocab chunk, so the last stage never
        # materializes (mb, S, V) logits — fwd OR bwd. One implementation
        # serves tp=1 and tp>1 (where it subsumes the vocab-parallel path:
        # same chunk loop per shard + the Megatron collective triple).
        # Resolution is per resolve_fused_ce ("auto": TPU + chunkable
        # vocab); the schedules all dispatch through _mb_loss, so the
        # gradient-identity contract is preserved by construction.
        from distributed_tensorflow_guide_tpu.ops.fused_ce import (
            resolve_fused_ce,
        )

        self.fused_ce = resolve_fused_ce(fused_ce,
                                         vocab_size=cfg.vocab_size)
        self.ce_chunk = ce_chunk
        # raw LN for the explicit-params head paths (fused CE at any tp;
        # vocab-parallel CE at tp>1) — the _Head module computes full-vocab
        # logits, which is exactly what those paths avoid
        self._head_ln = nn.LayerNorm(dtype=cfg.dtype)
        # 3D parallelism (dp x tp x pp): when the mesh's ``model`` axis is
        # >1, each pipeline stage's blocks are Megatron-TP-sharded over it —
        # qkv/up kernels column-parallel (heads / d_ff dims), proj/down
        # row-parallel, with the f/g conjugate operators inside the block
        # (models/transformer.py ``tp_axis``) keeping values AND gradients
        # exact inside this strategy's manual-SPMD shard_map. Params are
        # initialized at global shapes and sharded by per-leaf specs
        # (:meth:`param_specs`); each device applies a LOCAL-config block on
        # its (heads/tp, d_ff/tp) shard. The vocab-sized tables shard too:
        # the token embedding is a Megatron parallel embedding
        # (:meth:`_embed_tokens`) and the LM head computes vocab-parallel
        # cross-entropy (:meth:`_mb_loss_fused` with axis="model", or the
        # naive :meth:`_mb_loss_vocab_parallel` when fused CE is off) — no
        # device holds a full-vocab table or materializes full-vocab
        # logits.
        self.tp = sizes["model"]
        if self.tp > 1:
            if cfg.vocab_size % self.tp:
                raise ValueError(
                    f"vocab_size {cfg.vocab_size} must divide by tp "
                    f"{self.tp} (vocab-parallel head)"
                )
            self.block_apply = Block(cfg.tp_local(self.tp, axis="model"))
            abs_block = jax.eval_shape(
                self.block.init,
                jax.random.PRNGKey(0),
                jnp.zeros((1, cfg.max_len, cfg.d_model), cfg.dtype),
            )["params"]
            self._stage_specs_tp = jax.tree_util.tree_map_with_path(
                lambda path, _: self._stage_leaf_spec(path),
                nn.meta.unbox(abs_block),
            )
        else:
            self.block_apply = self.block

    # -- params ---------------------------------------------------------------
    def init_params(self, rng) -> dict:
        """Initialize and lay out onto the mesh. Single-controller path;
        multi-controller callers build global arrays from
        :meth:`init_host_params` (device_put cannot target another
        process's shards)."""
        return jax.device_put(
            self.init_host_params(rng), self.param_shardings()
        )

    def init_params_multihost(self, rng) -> dict:
        """Multi-controller init: every process computes the identical
        host tree (deterministic in ``rng``) and materializes ONLY its
        own shards via ``make_array_from_callback`` — the layout
        ``device_put`` cannot produce when shards live on another
        process's devices. Used by the cross-process pipeline test; the
        entry point for real multi-host training."""
        import numpy as np

        host = jax.tree.map(np.asarray, self.init_host_params(rng))
        full_specs = expand_prefix(self.param_specs(), host)
        return jax.tree.map(
            lambda h, spec: jax.make_array_from_callback(
                h.shape, NamedSharding(self.mesh, spec),
                lambda idx, h=h: h[idx],
            ),
            host, full_specs,
            is_leaf=lambda x: isinstance(x, np.ndarray),
        )

    def init_host_params(self, rng) -> dict:
        """The un-laid-out param tree (deterministic in ``rng`` — every
        process computes identical values, which is what lets
        :meth:`init_params_multihost` slice out per-process shards)."""
        cfg = self.cfg
        r_emb, r_blocks, r_head = jax.random.split(rng, 3)
        dummy_tok = jnp.zeros((1, cfg.max_len), jnp.int32)
        emb = self.embedder.init(r_emb, dummy_tok)["params"]
        dummy_x = jnp.zeros((1, cfg.max_len, cfg.d_model), cfg.dtype)

        keys = jax.random.split(r_blocks, cfg.num_layers)
        stacked = jax.vmap(
            lambda k: self.block.init(k, dummy_x)["params"]
        )(keys)
        v = self.virtual_chunks
        if v == 1:
            stacked = jax.tree.map(
                lambda x: x.reshape(
                    self.n_stages, self.layers_per_stage, *x.shape[1:]
                ),
                stacked,
            )
        else:
            # interleaved chunk order: global row r = s*v + j (the row the
            # contiguous pipe-shard hands device s as local row j) holds the
            # layers of chunk-stage k = j*P + s
            P_, Lc = self.n_stages, self.layers_per_chunk
            order = []
            for r in range(P_ * v):
                s, j = divmod(r, v)
                k = j * P_ + s
                order.extend(range(k * Lc, (k + 1) * Lc))
            idx = jnp.asarray(order)
            stacked = jax.tree.map(
                lambda x: x[idx].reshape(P_ * v, Lc, *x.shape[1:]),
                stacked,
            )
        head = self.head.init(r_head, dummy_x)["params"]
        return {"embed": emb, "stages": stacked, "head": head}

    @staticmethod
    def _stage_leaf_spec(path) -> P:
        """Megatron placement for one stacked stage leaf (dims: row, layer,
        *param). Column-parallel kernels shard their output dim (heads /
        d_ff), row-parallel their input dim; everything else replicates
        over ``model``."""
        names = tuple(
            k.key for k in path if isinstance(k, jax.tree_util.DictKey)
        )
        table = {
            ("attn", "qkv", "kernel"): P("pipe", None, None, None, "model"),
            ("attn", "proj", "kernel"): P("pipe", None, "model"),
            ("mlp", "up", "kernel"): P("pipe", None, None, "model"),
            ("mlp", "up", "bias"): P("pipe", None, "model"),
            ("mlp", "down", "kernel"): P("pipe", None, "model"),
        }
        return table.get(names[-3:], P("pipe"))

    def layout_metadata(self) -> dict:
        """Layout identity for checkpoints (``Checkpointer.save(layout=)``).

        The interleaved stacking permutes layer order inside
        ``params['stages']`` — a (P=2, v=2) tree is shape-identical to a
        (P=4, v=1) tree, so orbax would silently restore one into the
        other with the wrong layer order. This dict pins the layout so
        restore can refuse the mismatch."""
        return {
            "format": "pipelined_lm_stages",
            "n_stages": self.n_stages,
            "virtual_chunks": self.virtual_chunks,
            "layers_per_chunk": self.layers_per_chunk,
            "tp": self.tp,
        }

    def ppermute_bytes_per_step(self, microbatch_size: int) -> float:
        """Closed-form per-device ICI ``ppermute`` traffic of ONE train
        step: every microbatch's activation crosses each of the P−1
        stage boundaries once forward and its gradient once backward, so
        the ring-averaged per-device bytes are

            2 · M · (mb · S · d_model · itemsize) · (P − 1) / P

        — the pipeline leg of the interconnect roofline
        (``benchmarks/common.pipeline_ppermute_bytes`` is the same
        formula; equality pinned in tests/test_overlap.py). Zero at
        P = 1: a single stage hands nothing off."""
        import numpy as np

        act = (microbatch_size * self.cfg.max_len * self.cfg.d_model
               * np.dtype(self.cfg.dtype).itemsize)
        if self.n_stages <= 1:
            return 0.0
        return (2.0 * self.num_microbatches * act
                * (self.n_stages - 1) / self.n_stages)

    def param_specs(self) -> dict:
        """Spec tree: stage stack sharded over pipe (and, when the mesh has
        a ``model`` axis, Megatron-TP over it per leaf; the LM-head kernel
        vocab-sharded over it), rest replicated."""
        if self.tp > 1:
            return {
                "embed": {"tok_emb": P("model"), "pos_emb": P()},
                "stages": self._stage_specs_tp,
                "head": {"ln_f": P(), "lm_head": P(None, "model")},
            }
        return {"embed": P(), "stages": P("pipe"), "head": P()}

    def param_shardings(self):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            self.param_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )

    def opt_state_specs(self, tx: optax.GradientTransformation, params):
        """Specs for the optimizer state: moment trees (optax state nodes
        that mirror the param tree's structure) inherit the params' full
        spec tree; everything else (counts, scalars) replicates.

        Structural matching, not shape matching: under TP the per-leaf
        stage specs differ BETWEEN same-shaped leaves (e.g. ``mlp/up/bias``
        is model-sharded while an ``ln`` scale of the same shape is
        replicated — they collide whenever d_ff == d_model), which is
        exactly the case ``assign_by_shape``'s docstring disclaims."""
        full = expand_prefix(self.param_specs(), params)
        treedef_p = jax.tree.structure(params)

        def is_param_shaped(node) -> bool:
            try:
                return jax.tree.structure(node) == treedef_p
            except Exception:
                return False

        def specs_for(node):
            if is_param_shaped(node):
                return full
            return jax.tree.map(lambda _: P(), node)

        return jax.tree.map(
            specs_for, jax.eval_shape(tx.init, params),
            is_leaf=is_param_shaped,
        )

    # -- the schedule ---------------------------------------------------------
    def _stage_apply(self, stage_params, x):
        """Run this stage's layer blocks (scan over the stack's rows).

        The remat mode (``cfg.resolved_remat_mode``, settable through a
        core/precision.py policy) reaches the autodiff schedules here:
        under "block" the scan body is checkpointed per block, so
        GPipe/interleaved backward recomputes block internals from block
        boundaries instead of storing every intermediate — the same memory
        contract 1F1B gets from its manual per-stage recompute. The knob is
        deliberately NOT applied under 1F1B: its VJP already recomputes
        from the saved stage input, and checkpointing on top would just
        re-run each block once more per backward tick for no
        residual-memory gain. The "attention" mode (checkpoint only the
        attention sub-layer) lives INSIDE Block, so it applies uniformly to
        every schedule including 1F1B's per-tick recompute.
        prevent_cse=False as in models/transformer.py — the body lives
        inside lax.scan, where the CSE barriers are unnecessary.
        """

        def body(h, layer_params):
            return self.block_apply.apply({"params": layer_params}, h), None

        if (self.cfg.resolved_remat_mode == "block"
                and self.schedule != "1f1b"):
            body = jax.checkpoint(body, prevent_cse=False)
        out, _ = lax.scan(body, x, stage_params)
        return out

    def _embed_tokens(self, embed_params, tokens):
        """(B, S) int32 -> (B, S, D) cfg.dtype — THE embedding path, shared
        by the all-microbatch forward and the 1F1B embed-grad branches.

        Under TP the token table is vocab-sharded over ``model`` (Megatron
        parallel embedding): each device holds V/tp rows, looks up only
        the tokens that fall in its slice (masked gather), and one
        ``tp_allreduce`` (psum fwd, identity bwd — so each shard's rows
        receive exactly their own cotangents) assembles the full
        embedding. Positional table stays replicated (max_len × D is
        small)."""
        cfg = self.cfg
        if self.tp > 1:
            v_local = cfg.vocab_size // self.tp
            shard = lax.axis_index("model")
            W = embed_params["tok_emb"]["embedding"]  # (V/tp, D) local
            local_id = tokens - shard * v_local
            ok = (local_id >= 0) & (local_id < v_local)
            # cast to the activation dtype BEFORE the collective: matches
            # nn.Embed's compute dtype on the tp=1 path and halves the
            # psum's wire bytes under bf16
            e_local = (
                W[jnp.clip(local_id, 0, v_local - 1)]
                * ok[..., None].astype(W.dtype)
            ).astype(cfg.dtype)
            e = cc.tp_allreduce(e_local, "model")
            pos = embed_params["pos_emb"]["embedding"][
                jnp.arange(tokens.shape[1])
            ][None].astype(cfg.dtype)
            return e + pos
        return self.embedder.apply(
            {"params": embed_params}, tokens
        ).astype(cfg.dtype)

    def _embed_all(self, embed_params, tokens_mbs):
        """Embed all M microbatches at once: (M, mb, S) -> (M, mb, S, D)."""
        M, mb, S = tokens_mbs.shape
        flat = tokens_mbs.reshape(M * mb, S)
        e = self._embed_tokens(embed_params, flat)
        return e.reshape(M, mb, S, self.cfg.d_model)

    def _head_loss_sum(self, head_params, finals, tokens_mbs):
        """Sum of per-microbatch head losses — the single implementation
        both the plain and interleaved GPipe paths dispatch to on the last
        stage (a scan over microbatches, so logits memory stays at one)."""
        def body(acc, inp):
            x, toks = inp
            return acc + self._mb_loss(head_params, x, toks), None

        total, _ = lax.scan(body, jnp.float32(0.0), (finals, tokens_mbs))
        return total

    def _mb_loss(self, head_params, x, toks):
        """Head + next-token NLL for one microbatch's final activations.

        The single definition shared by every schedule — the schedules are
        contractually gradient-identical, so the loss math must not fork.
        With ``fused_ce`` on, the chunked fused cross-entropy serves tp=1
        AND tp>1 (one implementation, ``axis`` toggles the Megatron
        collectives); otherwise TP dispatches to the naive vocab-parallel
        cross-entropy and tp=1 to the full-logits head.
        """
        if self.fused_ce:
            return self._mb_loss_fused(head_params, x, toks)
        if self.tp > 1:
            return self._mb_loss_vocab_parallel(head_params, x, toks)
        logits = self.head.apply({"params": head_params}, x)
        logp = jax.nn.log_softmax(logits[:, :-1])
        ll = jnp.take_along_axis(
            logp, toks[:, 1:][..., None], axis=-1
        )[..., 0]
        return -jnp.mean(ll)

    def _mb_loss_fused(self, head_params, x, toks):
        """Chunked fused CE (ops/fused_ce.py): head matmul, online
        log-sum-exp, target gather and grad-of-logits all run per vocab
        chunk under one custom_vjp — no (mb, S, V) tensor live in fwd or
        bwd, which at GPT-2's 50304 vocab is the last stage's dominant
        HBM term. Under tp>1 the kernel is this device's vocab shard and
        ``axis="model"`` turns on the collective triple + dx psum,
        subsuming :meth:`_mb_loss_vocab_parallel`."""
        from distributed_tensorflow_guide_tpu.ops.fused_ce import (
            fused_next_token_loss,
        )

        xh = self._head_ln.apply({"params": head_params["ln_f"]}, x)
        kernel = head_params["lm_head"]["kernel"]  # (D, V/tp) local shard
        return fused_next_token_loss(
            xh, kernel, toks, chunk=self.ce_chunk,
            axis="model" if self.tp > 1 else None)

    def _mb_loss_vocab_parallel(self, head_params, x, toks):
        """Megatron vocab-parallel cross-entropy (Shoeybi et al. 2019 §3):
        the LM-head kernel is sharded over ``model`` along VOCAB, each
        device computes logits for its vocab slice only, and the NLL is
        assembled from three scalar-field collectives — max (stability),
        sum-exp (partition function), and the target logit (owned by
        exactly one shard). No device ever materializes (S, V) logits:
        peak logits memory drops by the TP degree, which at GPT-2's 50304
        vocab is the dominant activation on the last stage.

        Collective gradient discipline (same as the block f/g pairing):
        ``tp_allreduce`` (psum fwd, identity bwd) assembles the replicated
        scalars so each device's local-loss cotangent stays 1; the input
        ``x`` passes through ``tp_identity`` (identity fwd, psum bwd) so
        dx sums every shard's vocab-slice contribution; the stabilizer max
        is gradient-stopped (exact for logsumexp).
        """
        cfg = self.cfg
        f32 = jnp.float32
        v_local = cfg.vocab_size // self.tp
        shard = lax.axis_index("model")
        xh = self._head_ln.apply({"params": head_params["ln_f"]}, x)
        xh = cc.tp_identity(xh, "model")
        kernel = head_params["lm_head"]["kernel"]  # (D, V/tp) local shard
        # f32 head matmul — same computation dtype _Head's Dense pins
        z = xh[:, :-1].astype(f32) @ kernel.astype(f32)
        targets = toks[:, 1:]
        # stop_gradient BEFORE the collective: pmax has no differentiation
        # rule, and the logsumexp stabilizer is exact with zero gradient
        m = cc.pmax(
            lax.stop_gradient(jnp.max(z, axis=-1)), "model"
        )  # (B, S-1)
        sumexp = cc.tp_allreduce(
            jnp.sum(jnp.exp(z - m[..., None]), axis=-1), "model"
        )
        lse = jnp.log(sumexp) + m
        local_t = targets - shard * v_local
        in_shard = (local_t >= 0) & (local_t < v_local)
        t_clamped = jnp.clip(local_t, 0, v_local - 1)
        z_t_local = jnp.take_along_axis(
            z, t_clamped[..., None], axis=-1
        )[..., 0]
        z_t = cc.tp_allreduce(jnp.where(in_shard, z_t_local, 0.0), "model")
        return jnp.mean(lse - z_t)

    def _pipeline_loss(self, params, tokens_mbs):
        """Per-device pipeline forward + LM loss.

        tokens_mbs: (M, mb, S) — this data-shard's microbatches.
        Returns mean next-token loss over all microbatches.

        FLOP discipline (round-3 restructure): the embedder runs ONCE for all
        M microbatches and only on stage 0; the head runs ONCE per microbatch
        and only on the last stage. Both owner-only paths use ``lax.cond``,
        which executes a single branch at runtime — non-owning stages pay
        nothing. The tick loop itself contains only block compute + one
        neighbor ppermute; completed last-stage activations are carried out
        of the scan as its ys and consumed by a post-scan head loop (a scan
        over microbatches, so logits memory stays at one microbatch).
        """
        cfg = self.cfg
        M, mb, S = tokens_mbs.shape
        n_stages = self.n_stages
        stage = lax.axis_index("pipe")
        stage_params = jax.tree.map(lambda x: x[0], params["stages"])
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        embeds = lax.cond(
            stage == 0,
            lambda: self._embed_all(params["embed"], tokens_mbs),
            lambda: jnp.zeros((M, mb, S, cfg.d_model), cfg.dtype),
        )

        def tick(received, t):
            # stage 0 injects microbatch t (clamped during drain ticks)
            inject_idx = jnp.clip(t, 0, M - 1)
            x_inject = lax.dynamic_index_in_dim(
                embeds, inject_idx, axis=0, keepdims=False
            )
            x_in = jnp.where(stage == 0, x_inject, received)
            x_out = self._stage_apply(stage_params, x_in)
            received = cc.ppermute(x_out, "pipe", fwd)
            return received, x_out

        x0 = jnp.zeros((mb, S, cfg.d_model), cfg.dtype)
        _, taps = lax.scan(tick, x0, jnp.arange(M + n_stages - 1))
        # On the last stage, tick t completes microbatch m = t-(P-1); the
        # first P-1 ys are fill ticks on every stage.
        taps = taps[n_stages - 1:]  # (M, mb, S, d_model)

        loss_sum = lax.cond(
            stage == n_stages - 1,
            lambda: self._head_loss_sum(params["head"], taps, tokens_mbs),
            lambda: jnp.float32(0.0),
        )
        # LOCAL loss: nonzero only on the last stage. Do NOT psum here — the
        # transpose of psum under shard_map is another psum, which would
        # multiply every cotangent by n_stages. Differentiating the local
        # value is exact: cotangents reach earlier stages back through the
        # ppermute transposes (the backward pipeline). The caller psums the
        # VALUE for reporting.
        return loss_sum / M

    def _pipeline_loss_interleaved(self, params, tokens_mbs):
        """Interleaved-GPipe forward + LM loss (virtual_chunks > 1).

        Same contract as :meth:`_pipeline_loss` (autodiff produces the
        reversed drain), with each device cycling through its ``v`` layer
        chunks per the static table from :func:`_make_interleaved_schedule`.
        Landing buffer is a full (v*M) grid — the same order of memory as
        the autodiff residuals GPipe keeps anyway. Idle fill/drain ticks
        are FREE at runtime (``lax.cond`` executes one branch; note static
        FLOP counters that model cond as max-of-branches still charge
        them); embed and head stay owner-only and once-per-microbatch,
        preserving the round-3 FLOP discipline.
        """
        cfg = self.cfg
        M, mb, S = tokens_mbs.shape
        P_, v = self.n_stages, self.virtual_chunks
        Lc = self.layers_per_chunk
        stage = lax.axis_index("pipe")
        local_stack = params["stages"]  # (v, Lc, ...) per device
        fwd = [(i, (i + 1) % P_) for i in range(P_)]
        sched = _make_interleaved_schedule(M, P_, v)

        embeds = lax.cond(
            stage == 0,
            lambda: self._embed_all(params["embed"], tokens_mbs),
            lambda: jnp.zeros((M, mb, S, cfg.d_model), cfg.dtype),
        )
        x_zero = jnp.zeros((mb, S, cfg.d_model), cfg.dtype)
        buf0 = jnp.zeros((v * M, mb, S, cfg.d_model), cfg.dtype)

        def tick(carry, xs):
            buf, x_in = carry
            jr, mr, rf, rjr, rmr = xs
            j = jnp.take(jr, stage)
            m = jnp.take(mr, stage)

            # land last tick's arrival in its (chunk, microbatch) slot
            slot_r = jnp.take(rjr, stage) * M + jnp.take(rmr, stage)
            cur = lax.dynamic_index_in_dim(buf, slot_r, 0, keepdims=False)
            new = jnp.where(jnp.take(rf, stage).astype(bool), x_in, cur)
            buf = lax.dynamic_update_index_in_dim(buf, new, slot_r, 0)

            # this tick's op; lax.cond executes ONE branch, so idle
            # fill/drain ticks cost no chunk FLOPs (same discipline as the
            # 1F1B switch — collectives stay outside the cond)
            jc = jnp.clip(j, 0, v - 1)
            mc = jnp.clip(m, 0, M - 1)

            def run_chunk():
                x_src = lax.dynamic_index_in_dim(buf, jc * M + mc, 0,
                                                 keepdims=False)
                x_emb = lax.dynamic_index_in_dim(embeds, mc, 0,
                                                 keepdims=False)
                is_entry = (stage == 0) & (jc == 0)  # chunk-stage 0 injects
                x = jnp.where(is_entry, x_emb, x_src)
                chunk_params = jax.tree.map(
                    lambda p: lax.dynamic_index_in_dim(p, jc, 0,
                                                       keepdims=False),
                    local_stack,
                )
                return self._stage_apply(chunk_params, x)

            x_out = lax.cond(j >= 0, run_chunk, lambda: x_zero)
            nxt = cc.ppermute(x_out, "pipe", fwd)
            return (buf, nxt), x_out

        xs = tuple(
            jnp.asarray(sched[k])
            for k in ("jrow", "mbrow", "rflag", "rj", "rm")
        )
        (_, _), taps = lax.scan(tick, (buf0, x_zero), xs)

        # microbatch m's final activations appear on device P-1 at the tick
        # its last chunk-stage ran
        tick_idx = jnp.asarray(
            [sched["done"][P_ * v - 1][m] for m in range(M)], jnp.int32
        )
        finals = taps[tick_idx]  # (M, mb, S, d) — meaningful on stage P-1

        loss_sum = lax.cond(
            stage == P_ - 1,
            lambda: self._head_loss_sum(params["head"], finals, tokens_mbs),
            lambda: jnp.float32(0.0),
        )
        return loss_sum / M  # local; caller psums the VALUE (see above)

    # -- 1F1B schedule (manual VJP) -------------------------------------------
    def _loss_and_grads_1f1b(self, params, tokens_mbs):
        """Per-device 1F1B pipeline: ``(params, (M, mb, S)) -> (loss, grads)``.

        GPipe (``_pipeline_loss`` + ``jax.grad``) runs all M forwards, then
        all M backwards — activation residuals for every microbatch are live
        at the peak. 1F1B interleaves: after a warmup of min(P-s, M)
        forwards, each stage strictly alternates backward/forward, so at most
        ~P microbatches are ever in flight and the residual ring buffer is
        O(P), not O(M). The schedule is a STATIC table (``_make_1f1b_schedule``)
        consumed as scan xs — no data-dependent control flow reaches XLA; the
        per-tick op dispatch is one ``lax.switch``.

        Backward here is hand-written (jax.vjp per tick) because autodiff
        through the forward scan can only produce the all-forward-then-
        all-backward order. Stage backward recomputes its forward from the
        saved stage INPUT (per-stage remat — the 1F1B memory contract).
        Collectives stay OUTSIDE the switch: every tick unconditionally
        ppermutes one activation forward and one cotangent backward (zeros
        when idle), so every device always participates.
        """
        cfg = self.cfg
        M, mb, S = tokens_mbs.shape
        P_ = self.n_stages
        stage = lax.axis_index("pipe")
        stage_params = jax.tree.map(lambda x: x[0], params["stages"])
        fwd_perm = [(i, (i + 1) % P_) for i in range(P_)]
        bwd_perm = [(i, (i - 1) % P_) for i in range(P_)]
        sched = _make_1f1b_schedule(M, P_)
        R = sched["R"]

        embeds = lax.cond(
            stage == 0,
            lambda: self._embed_all(params["embed"], tokens_mbs),
            lambda: jnp.zeros((M, mb, S, cfg.d_model), cfg.dtype),
        )

        def stage_fn(sp, x):
            return self._stage_apply(sp, x)

        def last_stage_loss(sp, hp, x, toks):
            out = self._stage_apply(sp, x)
            return self._mb_loss(hp, out, toks) / M  # total loss = sum_m this

        f32 = jnp.float32
        zero_g = {
            "embed": jax.tree.map(lambda p: jnp.zeros(p.shape, f32),
                                  params["embed"]),
            "stage": jax.tree.map(lambda p: jnp.zeros(p.shape, f32),
                                  stage_params),
            "head": jax.tree.map(lambda p: jnp.zeros(p.shape, f32),
                                 params["head"]),
        }
        buf = jnp.zeros((R, mb, S, cfg.d_model), cfg.dtype)
        x_zero = jnp.zeros((mb, S, cfg.d_model), cfg.dtype)

        def tick(carry, xs):
            act_buf, cot_buf, resid_buf, act_in, cot_in, g_acc, loss_acc = carry
            op_row, mb_row, sa_row, sam_row, sc_row, scm_row = xs
            op = jnp.take(op_row, stage)
            m = jnp.take(mb_row, stage)

            # 1) land last tick's arrivals in their ring-buffer slots
            def land(buf_, val, flag, slot):
                cur = lax.dynamic_index_in_dim(buf_, slot, 0, keepdims=False)
                new = jnp.where(flag.astype(bool), val, cur)
                return lax.dynamic_update_index_in_dim(buf_, new, slot, 0)

            act_buf = land(act_buf, act_in, jnp.take(sa_row, stage),
                           jnp.take(sam_row, stage) % R)
            cot_buf = land(cot_buf, cot_in, jnp.take(sc_row, stage),
                           jnp.take(scm_row, stage) % R)

            slot = m % R
            toks = lax.dynamic_index_in_dim(
                tokens_mbs, jnp.clip(m, 0, M - 1), axis=0, keepdims=False
            )

            # 2) this tick's op
            def do_idle(resid_buf, g_acc, loss_acc):
                return resid_buf, g_acc, loss_acc, x_zero, x_zero

            def do_fwd(resid_buf, g_acc, loss_acc):
                x_prev = lax.dynamic_index_in_dim(act_buf, slot, 0,
                                                  keepdims=False)
                x_emb = lax.dynamic_index_in_dim(
                    embeds, jnp.clip(m, 0, M - 1), axis=0, keepdims=False
                )
                x_in = jnp.where(stage == 0, x_emb, x_prev)
                resid_buf = lax.dynamic_update_index_in_dim(
                    resid_buf, x_in, slot, 0
                )
                x_out = stage_fn(stage_params, x_in)
                return resid_buf, g_acc, loss_acc, x_out, x_zero

            def do_bwd(resid_buf, g_acc, loss_acc):
                x_in = lax.dynamic_index_in_dim(resid_buf, slot, 0,
                                                keepdims=False)

                def last_branch():
                    loss_m, vjp = jax.vjp(
                        lambda sp, hp, x: last_stage_loss(sp, hp, x, toks),
                        stage_params, params["head"], x_in,
                    )
                    d_sp, d_hp, dx = vjp(f32(1.0))
                    return loss_m, d_sp, d_hp, dx

                def mid_branch():
                    g_out = lax.dynamic_index_in_dim(cot_buf, slot, 0,
                                                     keepdims=False)
                    _, vjp = jax.vjp(stage_fn, stage_params, x_in)
                    d_sp, dx = vjp(g_out)
                    return f32(0.0), d_sp, zero_g["head"], dx

                loss_m, d_sp, d_hp, dx = lax.cond(
                    stage == P_ - 1, last_branch, mid_branch
                )

                def embed_branch():
                    _, evjp = jax.vjp(
                        lambda ep: self._embed_tokens(ep, toks),
                        params["embed"],
                    )
                    (d_emb,) = evjp(dx)
                    return jax.tree.map(lambda g: g.astype(f32), d_emb)

                d_emb = lax.cond(
                    stage == 0, embed_branch, lambda: zero_g["embed"]
                )
                g_acc = {
                    "embed": jax.tree.map(jnp.add, g_acc["embed"], d_emb),
                    "stage": jax.tree.map(
                        lambda a, g: a + g.astype(f32), g_acc["stage"], d_sp
                    ),
                    "head": jax.tree.map(
                        lambda a, g: a + g.astype(f32), g_acc["head"], d_hp
                    ),
                }
                return resid_buf, g_acc, loss_acc + loss_m, x_zero, dx

            resid_buf, g_acc, loss_acc, send_act, send_cot = lax.switch(
                op, [do_idle, do_fwd, do_bwd], resid_buf, g_acc, loss_acc
            )

            # 3) unconditional neighbor exchange (zeros when idle)
            act_in = cc.ppermute(send_act, "pipe", fwd_perm)
            cot_in = cc.ppermute(send_cot, "pipe", bwd_perm)
            return (act_buf, cot_buf, resid_buf, act_in, cot_in, g_acc,
                    loss_acc), None

        xs = tuple(
            jnp.asarray(sched[k]) for k in ("op", "mb", "sa", "sam", "sc",
                                            "scm")
        )
        (_, _, _, _, _, g_acc, loss_acc), _ = lax.scan(
            tick, (buf, buf, buf, x_zero, x_zero, zero_g, f32(0.0)), xs
        )
        grads = {
            "embed": g_acc["embed"],
            "stages": jax.tree.map(lambda g: g[None], g_acc["stage"]),
            "head": g_acc["head"],
        }
        return loss_acc, grads

    # -- interleaved 1F1B (manual VJP, v chunks per device) --------------------
    def _loss_and_grads_1f1b_interleaved(self, params, tokens_mbs):
        """Per-device interleaved-1F1B: Megatron's combined schedule
        (virtual chunks × 1F1B) as one static-table scan — the O(P)
        in-flight cap of :meth:`_loss_and_grads_1f1b` AND the ~v-fold
        bubble shrink of :meth:`_pipeline_loss_interleaved` together.

        Differences from the v=1 tick loop: the op dispatch carries a local
        chunk row ``j`` (chunk-stage k = j*P + s), chunk params are gathered
        from the (v, Lc, ...) local stack per tick, ring-buffer slots are
        keyed by (j*M + m) % R, and the embed/head ownership predicates
        sharpen from ``stage == 0`` / ``stage == P-1`` to chunk-stage 0 /
        chunk-stage D-1 (i.e. also require j == 0 / j == v-1). Collectives
        stay OUTSIDE the switch: one activation ppermute forward and one
        cotangent ppermute backward per tick, zeros when idle.
        """
        cfg = self.cfg
        M, mb, S = tokens_mbs.shape
        P_, v = self.n_stages, self.virtual_chunks
        stage = lax.axis_index("pipe")
        local_stack = params["stages"]  # (v, Lc, ...) per device
        fwd_perm = [(i, (i + 1) % P_) for i in range(P_)]
        bwd_perm = [(i, (i - 1) % P_) for i in range(P_)]
        sched = _make_interleaved_1f1b_schedule(M, P_, v)
        R = sched["R"]

        embeds = lax.cond(
            stage == 0,
            lambda: self._embed_all(params["embed"], tokens_mbs),
            lambda: jnp.zeros((M, mb, S, cfg.d_model), cfg.dtype),
        )

        def chunk_fn(cp, x):
            return self._stage_apply(cp, x)

        def last_chunk_loss(cp, hp, x, toks):
            out = self._stage_apply(cp, x)
            return self._mb_loss(hp, out, toks) / M

        f32 = jnp.float32
        zero_g = {
            "embed": jax.tree.map(lambda p: jnp.zeros(p.shape, f32),
                                  params["embed"]),
            "stages": jax.tree.map(lambda p: jnp.zeros(p.shape, f32),
                                   local_stack),
            "head": jax.tree.map(lambda p: jnp.zeros(p.shape, f32),
                                 params["head"]),
        }
        buf = jnp.zeros((R, mb, S, cfg.d_model), cfg.dtype)
        x_zero = jnp.zeros((mb, S, cfg.d_model), cfg.dtype)

        def tick(carry, xs):
            act_buf, cot_buf, resid_buf, act_in, cot_in, g_acc, loss_acc = carry
            (op_row, jr_row, mb_row, sa_row, saj_row, sam_row,
             sc_row, scj_row, scm_row) = xs
            op = jnp.take(op_row, stage)
            j = jnp.take(jr_row, stage)
            m = jnp.take(mb_row, stage)

            # 1) land last tick's arrivals in their (chunk, microbatch) slots
            def land(buf_, val, flag, jrow, mrow):
                slot = (jnp.take(jrow, stage) * M + jnp.take(mrow, stage)) % R
                cur = lax.dynamic_index_in_dim(buf_, slot, 0, keepdims=False)
                new = jnp.where(flag.astype(bool), val, cur)
                return lax.dynamic_update_index_in_dim(buf_, new, slot, 0)

            act_buf = land(act_buf, act_in, jnp.take(sa_row, stage),
                           saj_row, sam_row)
            cot_buf = land(cot_buf, cot_in, jnp.take(sc_row, stage),
                           scj_row, scm_row)

            slot = (j * M + m) % R
            is_first = (stage == 0) & (j == 0)        # chunk-stage 0
            is_last = (stage == P_ - 1) & (j == v - 1)  # chunk-stage D-1

            # The chunk-params gather and token slice live INSIDE the switch
            # branches (mirroring run_chunk in the gpipe-interleaved path):
            # lax.cond/switch executes one branch, so idle fill/drain ticks
            # pay neither the chunk-stack copy nor anything else.
            def gather_chunk():
                return jax.tree.map(
                    lambda p: lax.dynamic_index_in_dim(p, j, 0,
                                                       keepdims=False),
                    local_stack,
                )

            # 2) this tick's op
            def do_idle(resid_buf, g_acc, loss_acc):
                return resid_buf, g_acc, loss_acc, x_zero, x_zero

            def do_fwd(resid_buf, g_acc, loss_acc):
                chunk_params = gather_chunk()
                x_prev = lax.dynamic_index_in_dim(act_buf, slot, 0,
                                                  keepdims=False)
                x_emb = lax.dynamic_index_in_dim(embeds, m, axis=0,
                                                 keepdims=False)
                x_in = jnp.where(is_first, x_emb, x_prev)
                resid_buf = lax.dynamic_update_index_in_dim(
                    resid_buf, x_in, slot, 0
                )
                x_out = chunk_fn(chunk_params, x_in)
                return resid_buf, g_acc, loss_acc, x_out, x_zero

            def do_bwd(resid_buf, g_acc, loss_acc):
                chunk_params = gather_chunk()
                toks = lax.dynamic_index_in_dim(tokens_mbs, m, axis=0,
                                                keepdims=False)
                x_in = lax.dynamic_index_in_dim(resid_buf, slot, 0,
                                                keepdims=False)

                def last_branch():
                    loss_m, vjp = jax.vjp(
                        lambda cp, hp, x: last_chunk_loss(cp, hp, x, toks),
                        chunk_params, params["head"], x_in,
                    )
                    d_cp, d_hp, dx = vjp(f32(1.0))
                    return loss_m, d_cp, d_hp, dx

                def mid_branch():
                    g_out = lax.dynamic_index_in_dim(cot_buf, slot, 0,
                                                     keepdims=False)
                    _, vjp = jax.vjp(chunk_fn, chunk_params, x_in)
                    d_cp, dx = vjp(g_out)
                    return f32(0.0), d_cp, zero_g["head"], dx

                loss_m, d_cp, d_hp, dx = lax.cond(
                    is_last, last_branch, mid_branch
                )

                def embed_branch():
                    _, evjp = jax.vjp(
                        lambda ep: self._embed_tokens(ep, toks),
                        params["embed"],
                    )
                    (d_emb,) = evjp(dx)
                    return jax.tree.map(lambda g: g.astype(f32), d_emb)

                d_emb = lax.cond(
                    is_first, embed_branch, lambda: zero_g["embed"]
                )

                def acc_chunk(a, g):
                    cur = lax.dynamic_index_in_dim(a, j, 0, keepdims=False)
                    return lax.dynamic_update_index_in_dim(
                        a, cur + g.astype(f32), j, 0
                    )

                g_acc = {
                    "embed": jax.tree.map(jnp.add, g_acc["embed"], d_emb),
                    "stages": jax.tree.map(acc_chunk, g_acc["stages"], d_cp),
                    "head": jax.tree.map(
                        lambda a, g: a + g.astype(f32), g_acc["head"], d_hp
                    ),
                }
                return resid_buf, g_acc, loss_acc + loss_m, x_zero, dx

            resid_buf, g_acc, loss_acc, send_act, send_cot = lax.switch(
                op, [do_idle, do_fwd, do_bwd], resid_buf, g_acc, loss_acc
            )

            # 3) unconditional neighbor exchange (zeros when idle)
            act_in = cc.ppermute(send_act, "pipe", fwd_perm)
            cot_in = cc.ppermute(send_cot, "pipe", bwd_perm)
            return (act_buf, cot_buf, resid_buf, act_in, cot_in, g_acc,
                    loss_acc), None

        xs = tuple(
            jnp.asarray(sched[k]) for k in ("op", "jr", "mb", "sa", "saj",
                                            "sam", "sc", "scj", "scm")
        )
        (_, _, _, _, _, g_acc, loss_acc), _ = lax.scan(
            tick, (buf, buf, buf, x_zero, x_zero, zero_g, f32(0.0)), xs
        )
        return loss_acc, g_acc

    # -- compiled step --------------------------------------------------------
    def make_train_step(self, tx: optax.GradientTransformation, params,
                        *, donate: bool = True, steps_per_call: int = 1,
                        stacked_batch: bool = False):
        """``(opt_state, params, batch{tokens:(B,S)}) -> (opt_state, params,
        metrics)`` — B = n_data * num_microbatches * microbatch_size.
        ``params`` is used only to derive optimizer-state specs.

        ``steps_per_call > 1`` runs that many optimizer steps inside ONE
        compiled program (``lax.scan`` around the whole pipeline schedule) —
        the same dispatch-amortization knob as
        :meth:`DataParallel._compile_step`: each executable launch costs
        host time, and a pipeline step is ONE launch regardless of its
        microbatch count, so
        K inner steps cut per-step launch overhead K-fold. With
        ``stacked_batch`` the tokens carry a leading ``steps_per_call``
        axis (one batch slice per inner step — the real-training mode);
        otherwise the same tokens are re-used every inner step (synthetic
        benchmarking mode). Metrics are the LAST inner step's."""
        if steps_per_call < 1:
            raise ValueError(
                f"steps_per_call must be >= 1, got {steps_per_call}")
        if stacked_batch and steps_per_call == 1:
            raise ValueError(
                "stacked_batch requires steps_per_call > 1 (a stacked "
                "batch's leading axis is consumed one slice per inner step)")
        M = self.num_microbatches
        opt_specs = self.opt_state_specs(tx, params)

        def sm_step(opt_state, params, tokens):
            mbs = tokens.reshape(M, tokens.shape[0] // M, tokens.shape[1])
            if self.schedule == "1f1b" and self.virtual_chunks > 1:
                local_loss, grads = self._loss_and_grads_1f1b_interleaved(
                    params, mbs
                )
            elif self.schedule == "1f1b":
                local_loss, grads = self._loss_and_grads_1f1b(params, mbs)
            elif self.virtual_chunks > 1:
                local_loss, grads = jax.value_and_grad(
                    self._pipeline_loss_interleaved
                )(params, mbs)
            else:
                local_loss, grads = jax.value_and_grad(self._pipeline_loss)(
                    params, mbs
                )
            loss = cc.psum(local_loss, "pipe")  # value only; see _pipeline_loss
            # embed/head grads are nonzero only on their owning stage;
            # stage grads are per-stage (no pipe reduction needed)
            grads = {
                "embed": cc.psum(grads["embed"], "pipe"),
                "stages": grads["stages"],
                "head": cc.psum(grads["head"], "pipe"),
            }
            if self.n_data > 1:
                grads = cc.pmean(grads, "data")
                loss = cc.pmean(loss, "data")
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return opt_state, params, {"loss": loss}

        if steps_per_call == 1:
            body = sm_step
            tokens_spec = P("data")
        else:
            def body(opt_state, params, tokens):
                if stacked_batch and tokens.shape[0] != steps_per_call:
                    raise ValueError(
                        f"stacked tokens leading axis {tokens.shape[0]} != "
                        f"steps_per_call={steps_per_call}; the scan would "
                        "silently run a different number of optimizer steps")

                def inner(carry, xs):
                    o, p = carry
                    o, p, m = sm_step(o, p, tokens if xs is None else xs)
                    return (o, p), m

                (opt_state, params), ms = lax.scan(
                    inner, (opt_state, params),
                    tokens if stacked_batch else None,
                    length=None if stacked_batch else steps_per_call)
                return opt_state, params, jax.tree.map(lambda x: x[-1], ms)

            tokens_spec = P(None, "data") if stacked_batch else P("data")

        sharded = shard_map(
            body,
            mesh=self.mesh,
            in_specs=(opt_specs, self.param_specs(), tokens_spec),
            out_specs=(opt_specs, self.param_specs(), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())

    def make_eval_step(self):
        """``(params, tokens) -> {loss, perplexity}`` — the no-grad half for
        :class:`~distributed_tensorflow_guide_tpu.train.evaluation.Evaluator`
        (pass the param tree as the evaluator's ``state``). Forward-only
        GPipe traversal (a backward schedule is a training concern; the
        forward loss is schedule-independent), psum'd across stages,
        pmean'd across data shards."""
        M = self.num_microbatches

        def sm_eval(params, tokens):
            mbs = tokens.reshape(M, tokens.shape[0] // M, tokens.shape[1])
            if self.virtual_chunks > 1:
                local_loss = self._pipeline_loss_interleaved(params, mbs)
            else:
                local_loss = self._pipeline_loss(params, mbs)
            loss = cc.psum(local_loss, "pipe")
            if self.n_data > 1:
                loss = cc.pmean(loss, "data")
            return {"loss": loss, "perplexity": jnp.exp(loss)}

        sharded = shard_map(
            sm_eval,
            mesh=self.mesh,
            in_specs=(self.param_specs(), P("data")),
            out_specs=P(),
            check_vma=False,
        )
        return jax.jit(sharded)

    def to_serving_params(self, params) -> dict:
        """Pipeline param tree -> the flat ``models.transformer.Transformer``
        layout, so a pipeline-trained LM can be served by
        ``models/generation.py`` (or fine-tuned under any other strategy).

        Inverts the stage stacking of :meth:`init_params`: contiguous
        sharding (v=1) stores layers in global order; interleaved stacking
        stores row r = s*v + j as chunk-stage k = j*P + s, inverted here
        with the same index map. Works on host or on-device arrays (the
        gather is a pure indexing program); TP>1 params are global-shaped
        and convert unchanged. Logits parity is pinned by
        tests/test_pipeline.py::test_to_serving_params_logits_parity.
        """
        import numpy as np

        P_, v, Lc = self.n_stages, self.virtual_chunks, self.layers_per_chunk
        L = self.cfg.num_layers

        if v == 1:
            inv = None
        else:
            order = []
            for r in range(P_ * v):
                s, j = divmod(r, v)
                k = j * P_ + s
                order.extend(range(k * Lc, (k + 1) * Lc))
            inv = np.argsort(np.asarray(order))

        def unstack(x):  # (rows, Lc, ...) -> (L, ...) global layer order
            flat = x.reshape(L, *x.shape[2:])
            return flat if inv is None else flat[inv]

        stages = jax.tree.map(unstack, params["stages"])
        out = {
            "tok_emb": params["embed"]["tok_emb"],
            "pos_emb": params["embed"]["pos_emb"],
            "ln_f": params["head"]["ln_f"],
            "lm_head": params["head"]["lm_head"],
        }
        for i in range(L):
            out[f"block_{i}"] = jax.tree.map(lambda x, i=i: x[i], stages)
        return out

    def init_opt_state(self, tx, params):
        """Optimizer state materialized directly into its shard layout."""
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            self.opt_state_specs(tx, params),
            is_leaf=lambda x: isinstance(x, P),
        )
        with self.mesh:
            return jax.jit(tx.init, out_shardings=shardings)(params)


# ---- program contracts (analysis/) ------------------------------------------


def lint_contracts():
    """Contract for the GPipe train step with the fused-CE head: the
    no-full-logits memory pin (no f32 (mb*(S-1), V) intermediate anywhere
    in the schedule) plus the stage-boundary collective census — the
    counts are pinned at the 8-device (data=4, pipe=2, M=2) fixture."""
    from distributed_tensorflow_guide_tpu.analysis.contracts import (
        CostPin,
        CostSpec,
        DonationSpec,
        ProgramContract,
    )
    from distributed_tensorflow_guide_tpu.analysis.cost import closed_forms

    def _ppermute_expect():
        # per-microbatch boundary activation: (1, 32, 16) f32 per device.
        # The comm model counts the M useful handoffs per direction; the
        # static schedule rotates the ring every tick including the
        # (P-1) bubble ticks, so the trace carries (M+P-1)/M of the model
        m, p = 2, 2
        act_bytes = 1 * 32 * 16 * 4
        common = closed_forms()
        return (common.pipeline_ppermute_bytes(act_bytes, m, p)
                * (m + p - 1) / m)

    def _build():
        import jax
        import optax

        from distributed_tensorflow_guide_tpu.analysis.fixtures import (
            tiny_lm_cfg,
        )
        from distributed_tensorflow_guide_tpu.core.mesh import (
            MeshSpec,
            build_mesh,
        )

        # max_len=32 so one microbatch spans 31 target rows — ABOVE the
        # 16-row CE chunk; the vocab_rows floor can then admit the chunk
        # logits while still catching a full-logits regression
        cfg = tiny_lm_cfg(vocab_size=80, max_len=32)
        mesh = build_mesh(MeshSpec(data=4, pipe=2))
        pp = PipelinedLM(mesh, cfg, num_microbatches=2, fused_ce=True,
                         ce_chunk=16)
        params = jax.eval_shape(pp.init_host_params, jax.random.PRNGKey(0))
        tx = optax.sgd(0.1)
        opt_state = jax.eval_shape(tx.init, params)
        step = pp.make_train_step(tx, params, donate=True)
        tokens = jax.ShapeDtypeStruct((8, 32), "int32")
        return step, (opt_state, params, tokens)

    return [
        ProgramContract(
            name="pipeline_fused_ce_train_step",
            build=_build,
            policy="f32",
            vocab_dim=80,
            vocab_rows=17,  # > ce_chunk(16), <= microbatch rows (31)
            max_vocab_f32_elems=0,
            collectives={
                # one activation handoff + its backward transpose (M=2,
                # P=2 — the schedule fuses per-tick sends into one pair)
                "ppermute[pipe]": 2,
                # loss + embed-grad + head-grad reductions over pipe
                "psum[pipe]": 3,
                # grad-tree pmean + loss pmean over data
                "psum[data]": 2,
            },
            donation=DonationSpec(argnums=(0, 1)),
            sources=(
                "distributed_tensorflow_guide_tpu.parallel.pipeline",
                "distributed_tensorflow_guide_tpu.ops.fused_ce",
                "distributed_tensorflow_guide_tpu.collectives.collectives",
            ),
            cost=CostSpec(
                pins=(
                    CostPin("collective_bytes[ppermute[pipe]]",
                            _ppermute_expect,
                            note="stage-boundary ring traffic incl. the "
                                 "bubble-tick rotations"),
                ),
                # 549,822 observed per device (params + M in-flight
                # microbatch activation stacks + fused-CE bwd workspace)
                max_peak_live_bytes=655360),
            notes="GPipe schedule + fused-CE head: no full logits, "
                  "bounded stage-boundary traffic"),
    ]
