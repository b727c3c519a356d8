"""Continuous-batching serving engine over the paged KV pool.

Exactly TWO programs serve every request mix, the prefill one compiled at
three widths, and neither ever retraces as the population changes:

* ``serve_decode_step`` — all ``slots`` rows advance one token. Each
  slot feeds its pending token at its own write position (the ``(B,)``
  index vector), writes its k/v through its block table, and samples the
  next token with the request's position-derived key. Empty and
  mid-prefill slots ride along with all-trash tables: their writes land
  in the trash block, the causal mask zeroes whatever they read, and the
  host discards their samples.
* ``serve_prefill_chunk_step`` — every prompt that waits, up to four,
  advances by one ``prefill_chunk``-token chunk, a row each (static
  chunk width; the chunk is just a C>1 decode through the same
  ``_paged_decode_attend`` path). The rows are padded to the next of
  ``PREFILL_WIDTHS``, and every width is compiled before the first
  prefill launch returns. Long prompts stream through in chunks
  interleaved with decode steps, one prefill launch between two decode
  launches, so admission never stalls resident streams for a whole
  prefill. The final chunk's sample at the prompt's last valid position
  IS the request's first generated token, whatever else the launch
  carried.

Both programs are pool -> pool: the cache pool is donated and returned,
so XLA aliases it in place (the state->state analogue of the one-shot
decode cache's scratch donation). Sampling keys derive from
(request rng, absolute position) — ``fold_in(rng, p)`` for the token at
position ``p`` — which makes every per-request stream bitwise identical
to a one-shot ``make_generate_fn`` run of that request alone, no matter
how scheduling interleaved it (the engine-vs-one-shot parity tests pin
this, greedy and sampled, across the decode levers).

One launch in flight (PR 34): a tick dispatches its launch and only then
fetches the tokens of the launch BEFORE it, so the device runs one program
while the host hands out the last one's tokens and plans the next. That
takes two things. The decode program's ``last_tok`` is a device vector the
engine keeps (``_pending``: a launch's tokens are merged into it by one
small jitted ``where``, never fetched for it), and the scheduler's
bookkeeping is advanced at the dispatch with the token's value left open
(scheduler.py: nothing but ``emitted`` and ``pending`` depends on the
value). :meth:`ServeEngine.settle` fetches and applies what is owed; the
engine calls it before anything that reads a token's value, and at once
for a model whose bookkeeping depends on what comes back (``MoEMLP``'s
overflow flags). A launch's events are returned by the ``step()`` call
after the one that dispatched it.

Serving under fire (PR 11) — the same position-derived keys are what
make every recovery path *bitwise-safe*:

* a transient launch failure (injected ``serve_step_exception`` or a
  real one) is retried through the shared ``retry_with_backoff`` — the
  tick's inputs are rebuilt from host state, so the re-run IS the
  original tick;
* a hung compiled step becomes :class:`WatchdogTimeout` (pass
  ``step_deadline_s``) instead of a silent stall, and retries like any
  transient;
* cancellation / TTFT / total deadlines are swept at step boundaries
  (``Scheduler.sweep``) — slot+blocks free with ``check_leaks`` clean;
* overload is refused at the door (queue-depth gate in the scheduler,
  predicted-TTFT gate here) with the retriable
  :class:`EngineOverloaded`;
* :meth:`ServeEngine.save_snapshot` serializes all HOST state through
  the manifested/CRC-verified checkpoint path; a killed engine
  restores the newest valid snapshot and every in-flight stream
  continues bitwise identical to an uninterrupted run — the block pool
  is never saved, residents simply re-prefill (the preemption path).
"""

from __future__ import annotations

import dataclasses
import glob
import io as _io
import json
import math
import os
import time
import zlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_guide_tpu.core.dist import retry_with_backoff
from distributed_tensorflow_guide_tpu.models.generation import (
    decode_config,
    sample_rows,
)
from distributed_tensorflow_guide_tpu.models.transformer import (
    WINDOW_LEAVES,
    Transformer,
    TransformerConfig,
)
from distributed_tensorflow_guide_tpu.obs import events as obs_events
from distributed_tensorflow_guide_tpu.obs.tracing import span, watch_stalls
from distributed_tensorflow_guide_tpu.serve import program_cache
from distributed_tensorflow_guide_tpu.serve.paged_cache import (
    BlockStore,
    table_row,
)
from distributed_tensorflow_guide_tpu.serve.prefix_index import CACHE_RID
from distributed_tensorflow_guide_tpu.serve.scheduler import (
    PREFILL,
    EngineOverloaded,
    Request,
    Scheduler,
)
from distributed_tensorflow_guide_tpu.testing.chaos import ChaosInjectedError
from distributed_tensorflow_guide_tpu.utils.watchdog import (
    Watchdog,
    WatchdogTimeout,
)

__all__ = ["Event", "Request", "ServeEngine", "EngineOverloaded",
           "WatchdogTimeout", "build_step_fns", "paged_cache_pool",
           "adapter_bank_shapes", "init_adapter_bank", "lint_contracts"]

# pool-pressure chaos faults allocate under this reserved owner id (real
# rids are non-negative) and release after this many engine ticks
_CHAOS_RID = -7
_PRESSURE_HOLD_TICKS = 4

# The rows a prefill launch may have. A launch carries the next chunk of
# every waiting prompt, up to the widest, padded to the next width: a chunk
# of 128 rows gives each weight byte 128 multiply-adds where the v5e's ridge
# is 240, so a second prompt's chunk rides on bytes already read, and a
# padded row costs what a real one does, so the ladder is short.
PREFILL_WIDTHS = (1, 2, 4)
# On the CPU a launch carries one prompt. Nothing there is bound by the
# weights' bytes, and XLA's CPU products round by the extent of their row
# axis (a chunk's numbers differ in the last bits with who shared its
# launch: 1e-6 in float32, a routing choice now and then in bfloat16), while
# what the CPU runs is tests that hold a request's tokens to references
# computed for the request alone. A test of the wide programs sets this to
# ``PREFILL_WIDTHS``.
CPU_PREFILL_WIDTHS = (1,)


@dataclasses.dataclass(frozen=True)
class Event:
    """One streamed token: ``first`` marks the request's first generated
    token (TTFT edge), ``done`` its completion. Terminal lifecycle events
    (cancellation, deadline breach) carry ``token == -1``, ``done=True``
    and ``status`` in {"cancelled", "expired"}; real tokens are
    ``status == "ok"``."""

    time: float
    rid: int
    token: int
    first: bool
    done: bool
    status: str = "ok"


def paged_config(cfg: TransformerConfig, *, num_blocks: int,
                 block_size: int,
                 prefill_chunk: int | None = None) -> TransformerConfig:
    """The serving view of a training config, paged flavour. A model with
    window layers also gets the size of a slot's ring
    (:func:`window_ring`), which needs the prefill chunk's length."""
    ring = None
    if cfg.window is not None:
        if prefill_chunk is None:
            raise ValueError("a model with window layers is paged for a "
                             "prefill chunk's length: give prefill_chunk")
        ring = window_ring(cfg.window, block_size, prefill_chunk)
    return dataclasses.replace(decode_config(cfg),
                               paged_num_blocks=num_blocks,
                               paged_block_size=block_size,
                               window_ring=ring)


def window_ring(window: int, block_size: int, prefill_chunk: int) -> int:
    """The positions of keys and values a slot keeps for a window layer,
    whatever the sequence's length: the ``window - 1`` before a prefill
    chunk's first query and the chunk itself (so that a chunk, written
    before it is read, overwrites no key its own first queries still see),
    rounded up to whole blocks and whole chunks (a chunk starts at a
    multiple of its length and never straddles the ring's end)."""
    unit = math.lcm(block_size, prefill_chunk)
    return -(-(window - 1 + prefill_chunk) // unit) * unit


def paged_cache_shapes(pcfg: TransformerConfig, slots: int):
    """Abstract tree of the paged pool — derived from the model exactly
    like generation.cache_shapes, so the allocated pool can never drift
    from what the step programs trace. Pool leaves are (num_blocks, ...)
    — independent of the batch width, which is what lets the S-slot
    decode program and the B=1 prefill program share one pool."""
    return _serving_shapes(pcfg, slots).get("cache", {})


def _serving_shapes(pcfg: TransformerConfig, slots: int):
    """Abstract trees of every collection a ``slots``-row decode call of the
    serving model makes: ``cache`` (the block pool) and, for a model whose
    sequences carry state beside keys and values, ``state``."""
    model = Transformer(pcfg)
    n_blk = pcfg.max_len // pcfg.paged_block_size
    rows = jnp.zeros((slots,), jnp.int32)
    extra = {"valid": rows} if pcfg.layers is not None else {}
    return jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((slots, 1), jnp.int32), rows,
            block_tables=jnp.zeros((slots, n_blk), jnp.int32), **extra))


def slot_state(pcfg: TransformerConfig, slots: int, device=None):
    """The zeroed per-slot state leaves beside the block pool (``{}`` for a
    model with none): row ``i`` of every leaf is slot ``i``'s, which both
    step programs address (the decode program's batch row ``i`` is slot
    ``i``; the prefill program is told its slot as it is told its block
    table). A slot's row needs no clearing between requests: a chunk that
    starts at position 0 reads zeros."""
    return _zeros(_serving_shapes(pcfg, slots).get("state", {}), device)


def _tree_bytes(tree) -> int:
    """The bytes of a tree's array leaves (0 for None or ``{}``)."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def _state_bytes(state) -> dict:
    """``window_bytes``, the window layers' rings (the ``state``
    collection's ``WINDOW_LEAVES``), and ``state_bytes``, its other leaves,
    the recurrent mixers': the two kinds of storage beside the block pool,
    neither of which grows with a sequence."""
    window = total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(state or {}):
        total += int(leaf.nbytes)
        if getattr(path[-1], "key", None) in WINDOW_LEAVES:
            window += int(leaf.nbytes)
    return {"state_bytes": total - window, "window_bytes": window}


def _zeros(shapes, device):
    """A zeroed tree of ``shapes``, committed to ``device`` (None leaves it
    uncommitted where JAX's default puts it)."""
    with jax.default_device(device):
        tree = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    return tree if device is None else jax.device_put(tree, device)


def paged_cache_pool(pcfg: TransformerConfig, slots: int, device=None):
    """Allocate the zeroed block pool, committed to ``device`` so that the
    pool-only programs (spill gather/scatter) run there too; None leaves it
    uncommitted where JAX's default puts it."""
    return _zeros(paged_cache_shapes(pcfg, slots), device)


def params_device(params):
    """The one device every array leaf of ``params`` lives on, or None when
    they span several (a mesh-sharded tree: placement is the sharding's) or
    there are no device arrays to ask."""
    devices = set()
    for leaf in jax.tree.leaves(params):
        if isinstance(leaf, jax.Array):
            devices |= leaf.devices()
    return next(iter(devices)) if len(devices) == 1 else None


def adapter_bank_shapes(cfg: TransformerConfig):
    """Abstract tree of the multi-LoRA (A, B) delta banks (the flax
    "adapters" collection) — derived from the model exactly like the
    pool so user-supplied banks can never drift from what the step
    programs trace. Bank shapes are independent of slots/paging (each
    site is ``(lora_adapters + 1, d_in, rank)`` x ``(..., rank, d_out)``),
    so any config with the same lora geometry yields the same tree.
    Requires ``cfg.lora_rank``."""
    if cfg.lora_rank is None:
        raise ValueError("adapter_bank_shapes requires cfg.lora_rank")
    model = Transformer(cfg)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                               jnp.zeros((1, 1), jnp.int32))
    return variables["adapters"]


def init_adapter_bank(cfg: TransformerConfig):
    """A zeroed adapter bank: every id (including every non-zero one)
    starts bitwise-base until its rows are written."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        adapter_bank_shapes(cfg))


@jax.jit
def _pool_gather(pool, idx):
    """KV spill d2h: rows ``idx`` of every pool leaf, ONE dispatch for
    the whole tree.  Not a step program — jit-cached per (pool shapes,
    idx width); the engine pads every batch to a multiple of 8 so only
    one width ever compiles, at init-warmup time."""
    return [leaf[idx] for leaf in jax.tree.leaves(pool)]


@jax.jit
def _pool_scatter(pool, idx, rows):
    """KV spill h2d: write ``rows[i]`` into leaf ``i`` at ``idx``, ONE
    dispatch for the whole tree.  Functional — the donated pool the
    step programs alias is never mutated in place.  Duplicate indices
    (the trash-block padding) all carry identical rows, so the scatter
    stays deterministic."""
    leaves, treedef = jax.tree.flatten(pool)
    return jax.tree.unflatten(
        treedef, [leaf.at[idx].set(r.astype(leaf.dtype))
                  for leaf, r in zip(leaves, rows)])


@jax.jit
def _merge_tokens(pending, tokens, rows):
    """The slots' pending tokens after a decode launch: the rows of
    ``rows`` take the launch's ``tokens`` (or what the host knows and the
    device does not), the others keep theirs. Dispatched after the launch,
    never waited for."""
    return jnp.where(rows, tokens, pending)


@jax.jit
def _place_tokens(pending, tokens, slots):
    """The slots' pending tokens after a prefill launch: row ``r``'s sample
    is slot ``slots[r]``'s first token, and a row whose prompt has chunks
    left, or a padding row, says a slot past the last and is dropped."""
    return pending.at[slots].set(tokens, mode="drop")


@dataclasses.dataclass
class _Launch:
    """One dispatched launch until it is settled: what the device will
    hand back, and what the scheduler was told with the values left open."""

    kind: str
    arg: list  # the slots of a prefill launch's rows, or the decode rows
    ids: dict  # the spans' tick (and a prefill launch's rids)
    now: float
    t0: float
    outs: tuple  # (tokens, [overflowed,] [census ...]) on the device
    payload: dict | None  # the recorder's launch identity
    keys: dict  # a decode launch's keys, for its ``engine.apply`` span
    produced: list | None = None  # None: not advanced until it is fetched
    owed: list = dataclasses.field(default_factory=list)
    # where in the launch's tokens each produced event's value lies
    took: list = dataclasses.field(default_factory=list)


def _routed_counters(load: np.ndarray, first: int = 0,
                     count: int | None = None) -> dict:
    """One launch's routed census, (routed layers, experts) over ALL the
    experts, as the numbers its ``engine.apply`` span carries, for a
    program that holds the experts ``[first, first + count)`` (all by
    default): ``assignments``, every live row's choices in every routed
    layer, and ``held_assignments``, those that fell to experts held here
    (the others add nothing in this program); ``experts_touched``, the
    distinct held experts a routed layer read (mean over the layers), and
    ``load_ratio``, the busiest held expert's assignments over the mean
    held expert's (mean over the layers; 1 is even). ``{}`` with no routed
    layer or no live row."""
    if not load.size or not load.sum():
        return {}
    held = load[:, first:None if count is None else first + count]
    out = {"assignments": int(load.sum()),
           "held_assignments": int(held.sum())}
    if held.sum():
        busy = held[held.sum(axis=1) > 0]
        out["experts_touched"] = float((held > 0).sum(axis=1).mean())
        out["load_ratio"] = float((busy.max(axis=1)
                                   / busy.mean(axis=1)).mean())
    return out


def _moe_fold(stats):
    """Fold the model's per-layer ``moe_stats`` sow tree into
    ``(load (E,), overflow (E,), overflow_tok (T,))`` — each summed over
    layers (sow appends one tuple entry per MoEMLP site). Runs inside the
    jitted step, so the engine gets three small arrays back instead of a
    nested per-block tree."""
    import collections.abc as _abc

    load, overflow, of_tok = [], [], []

    def walk(node):
        if isinstance(node, _abc.Mapping):
            if "load" in node and "overflow" in node:
                load.extend(node["load"])
                overflow.extend(node["overflow"])
                of_tok.extend(node["overflow_tok"])
            else:
                for k in sorted(node):
                    walk(node[k])

    walk(stats)
    return sum(load), sum(overflow), sum(of_tok)


def _routed_fold(stats):
    """The routed layers' sown census, ``{"block_<i>": {"mlp": {"load":
    (E,)}}}``, as one (routed layers, experts) int32 array with the layers
    in the model's order; (0, 0) for a model with none."""
    layers = sorted(stats, key=lambda name: int(name.rpartition("_")[2]))
    if not layers:
        return jnp.zeros((0, 0), jnp.int32)
    return jnp.stack([stats[name]["mlp"]["load"][0] for name in layers])


_STEP_FNS = {}


def build_step_fns(cfg: TransformerConfig, *, slots: int, num_blocks: int,
                   block_size: int, prefill_chunk: int,
                   temperature: float = 0.0, top_k: int | None = None):
    """Build the two jitted step programs (shared by the engine and the
    lint contracts, so what the linter audits is what serves).

    Memoized on everything that reaches the trace: config (which carries
    the pool geometry), sampling knobs, and the donation gate. ``slots``
    and ``prefill_chunk`` deliberately do NOT key the memo — the jitted
    programs shape-specialize on their arguments, so engines that differ
    only in slot count or chunk width share one traced pair, and
    spinning an engine up with a geometry already served compiles
    nothing at all."""
    donate = jax.default_backend() != "cpu"
    pcfg = paged_config(cfg, num_blocks=num_blocks, block_size=block_size,
                        prefill_chunk=prefill_chunk)
    # a window model's ring is whole prefill chunks: the one way the
    # chunk's length reaches a trace (``pcfg.window_ring``, None otherwise)
    memo_key = (cfg, num_blocks, block_size, temperature, top_k, donate,
                pcfg.window_ring)
    hit = _STEP_FNS.get(memo_key)
    if hit is not None:
        return hit
    model = Transformer(pcfg)
    n_blk = pcfg.max_len // block_size
    lora = pcfg.lora_rank is not None
    moe = pcfg.moe

    def chunk_samples(logits, start, valid, keys):
        """A prefill launch's tokens, (R,), from the (R, 1, V) logits at
        each row's last valid position (``last_valid``): row ``r``'s sample
        under its own key for the absolute position ``start[r] +
        valid[r]``, on a prompt's final chunk exactly the one-shot prefill
        sample at position P, whatever else the launch carried. A padding
        row (``valid`` 0) samples its first position; the host discards
        it."""
        pos_keys = jax.vmap(jax.random.fold_in)(keys, start + valid)
        return sample_rows(logits[:, 0], pos_keys, temperature, top_k)

    def last_valid(valid):
        return jnp.maximum(valid - 1, 0)

    patterned = pcfg.layers is not None
    if patterned:
        # still exactly two jitted programs. A patterned model's pair
        # threads two states, the block pool and the per-slot state leaves
        # (``{}`` where no mixer has any), and hands back the routed
        # layers' census of the launch, (routed layers, experts): idle
        # decode rows and a chunk's padding rows are told apart by
        # ``valid``, route nowhere and leave their slot's state alone.
        def decode_step(params, pool, state, tables, written, last_tok,
                        keys):
            logits, mut = model.apply(
                {"params": params, "cache": pool, "state": state},
                last_tok[:, None], written, block_tables=tables,
                valid=(written > 0).astype(jnp.int32),
                mutable=["cache", "state", "routed_stats"])
            pos_keys = jax.vmap(jax.random.fold_in)(keys, written + 1)
            nxt = sample_rows(logits[:, -1], pos_keys, temperature, top_k)
            return (nxt, mut.get("cache", pool), mut.get("state", state),
                    _routed_fold(mut.get("routed_stats", {})))

        def prefill_chunk_step(params, pool, state, tables, start, chunk,
                               valid, keys, slots):
            logits, mut = model.apply(
                {"params": params, "cache": pool, "state": state},
                chunk, start, block_tables=tables, state_rows=slots,
                valid=valid, last=last_valid(valid),
                mutable=["cache", "state", "routed_stats"])
            return (chunk_samples(logits, start, valid, keys),
                    mut.get("cache", pool), mut.get("state", state),
                    _routed_fold(mut.get("routed_stats", {})))
    elif lora:
        # still exactly two jitted programs: the LoRA engine's pair takes
        # two extra operands — the shared (A, B) delta banks and the
        # per-slot adapter-id vector — and every slot's delta is gathered
        # by id inside the one compiled step (no per-adapter programs)
        def decode_step(params, pool, tables, written, last_tok, keys,
                        adapters, adapter_ids):
            logits, mut = model.apply(
                {"params": params, "cache": pool, "adapters": adapters},
                last_tok[:, None], written, block_tables=tables,
                adapter=adapter_ids, mutable=["cache"])
            pos_keys = jax.vmap(jax.random.fold_in)(keys, written + 1)
            nxt = sample_rows(logits[:, -1], pos_keys, temperature, top_k)
            return nxt, mut["cache"]

        def prefill_chunk_step(params, pool, tables, start, chunk, valid,
                               keys, adapters, adapter_ids):
            logits, mut = model.apply(
                {"params": params, "cache": pool, "adapters": adapters},
                chunk, start, block_tables=tables, adapter=adapter_ids,
                last=last_valid(valid), mutable=["cache"])
            return chunk_samples(logits, start, valid, keys), mut["cache"]
    elif moe:
        # still exactly two jitted programs: the MoE pair runs the router
        # dispatch INSIDE the step (mutable=["moe_stats"] so the sown
        # census comes back) and returns the per-slot overflow flags the
        # engine's stall-and-retry loop consumes. Idle slots are masked
        # out of routing (written == 0), so a garbage slot can never
        # consume a capacity seat a live slot needs.
        def decode_step(params, pool, tables, written, last_tok, keys):
            logits, mut = model.apply(
                {"params": params, "cache": pool},
                last_tok[:, None], written, block_tables=tables,
                moe_mask=written > 0, mutable=["cache", "moe_stats"])
            load, overflow, of_tok = _moe_fold(mut["moe_stats"])
            pos_keys = jax.vmap(jax.random.fold_in)(keys, written + 1)
            nxt = sample_rows(logits[:, -1], pos_keys, temperature, top_k)
            return nxt, mut["cache"], of_tok > 0, load, overflow

        def prefill_chunk_step(params, pool, tables, start, chunk, valid,
                               keys):
            # the dispatch buffer widens to the launch's rows x the chunk
            # length (MoEMLP: multi-token calls are dropless by
            # construction), so a prefill chunk can never overflow — only
            # pad rows past ``valid`` (a padding row: all of them) are
            # masked out of the census
            mask = jnp.arange(chunk.shape[1])[None, :] < valid[:, None]
            logits, mut = model.apply(
                {"params": params, "cache": pool},
                chunk, start, block_tables=tables, moe_mask=mask,
                last=last_valid(valid), mutable=["cache", "moe_stats"])
            load, overflow, _ = _moe_fold(mut["moe_stats"])
            return (chunk_samples(logits, start, valid, keys), mut["cache"],
                    load, overflow)
    else:
        def decode_step(params, pool, tables, written, last_tok, keys):
            """(S,) tokens in, (S,) tokens out; pool threaded
            state->state."""
            logits, mut = model.apply(
                {"params": params, "cache": pool},
                last_tok[:, None], written, block_tables=tables,
                mutable=["cache"])
            pos_keys = jax.vmap(jax.random.fold_in)(keys, written + 1)
            nxt = sample_rows(logits[:, -1], pos_keys, temperature, top_k)
            return nxt, mut["cache"]

        def prefill_chunk_step(params, pool, tables, start, chunk, valid,
                               keys):
            """One (R, prefill_chunk) launch: the next chunk of R prompts,
            a row each. ``valid[r]`` is how many positions of row ``r`` are
            real prompt (the rest are pads whose writes land inside the
            admitted blocks and are either overwritten by decode before
            anything attends them, or masked forever; a padding ROW has
            none, and an all-trash table); the samples are
            ``chunk_samples``'."""
            logits, mut = model.apply(
                {"params": params, "cache": pool},
                chunk, start, block_tables=tables,
                last=last_valid(valid), mutable=["cache"])
            return chunk_samples(logits, start, valid, keys), mut["cache"]

    # donation intent is (1,) — the pool — for both programs; the CPU
    # backend doesn't implement input-output aliasing, same gate as
    # make_generate_fn
    donated = (1, 2) if patterned else (1,)  # the pool (and the state)
    decode_jit = jax.jit(decode_step,
                         donate_argnums=donated if donate else ())
    prefill_jit = jax.jit(prefill_chunk_step,
                          donate_argnums=donated if donate else ())
    fns = SimpleNamespace(
        decode=decode_jit, prefill=prefill_jit, model=model, cfg=pcfg,
        n_blk=n_blk, declared_donate_argnums=donated, donates_pool=donate,
        temperature=temperature, top_k=top_k, lora=lora, moe=moe,
        patterned=patterned, static=memo_key, programs={},
        compiled_widths=set())
    _STEP_FNS[memo_key] = fns
    return fns


class ServeEngine:
    """The serving loop: host scheduling around the two static programs.

    >>> eng = ServeEngine(cfg, params, slots=4, num_blocks=33,
    ...                   block_size=8, prefill_chunk=16)
    >>> eng.submit(Request(rid=0, prompt=toks, max_new_tokens=16,
    ...                    rng=jax.random.PRNGKey(0), arrival=0.0))
    >>> events = eng.run()          # drain everything (virtual time)
    >>> eng.completions()[0]        # the request's generated tokens
    """

    def __init__(self, cfg: TransformerConfig, params, *, slots: int,
                 num_blocks: int, block_size: int, prefill_chunk: int,
                 temperature: float = 0.0, top_k: int | None = None,
                 max_queue: int | None = None,
                 chaos=None, burst_factory=None,
                 step_deadline_s: float | None = None,
                 retry_attempts: int = 3,
                 retry_base_delay_s: float = 0.05,
                 snapshot_dir=None, snapshot_keep: int = 3,
                 prefix_cache: bool = False,
                 host_blocks: int = 0, persist_cache: bool = False,
                 tenant_quotas=None, drr_quantum: int | None = None,
                 adapters=None, recorder=None,
                 online_tune: bool | None = None):
        # collections and compiles as spans of their own, counted on each
        # tick's span (obs/tracing.watch_stalls: once a process)
        self._stalls = watch_stalls()
        # online in-situ autotuning (round 21): True/False set the
        # process-wide override (the tuning table is process state, so
        # the knob is too — autotune.set_online_tune), None inherits the
        # DTG_ONLINE_TUNE env gate. On a sweep-capable backend the first
        # trace of an unseen (kernel, shape, dtype, device_kind) key then
        # pays one bounded sweep during warmup instead of falling back to
        # defaults; on CPU this is always a no-op (hermeticity contract).
        if online_tune is not None:
            from distributed_tensorflow_guide_tpu.ops import autotune
            autotune.set_online_tune(online_tune)
        if cfg.weight_dtype == "fp8":
            from distributed_tensorflow_guide_tpu.core.precision import (
                require_fp8,
            )
            require_fp8()
        self.fns = build_step_fns(
            cfg, slots=slots, num_blocks=num_blocks,
            block_size=block_size, prefill_chunk=prefill_chunk,
            temperature=temperature, top_k=top_k)
        self.params = params
        # The engine lives where its weights live: the pool and the adapter
        # bank are allocated on the params' device and the host operands of
        # each tick go in as numpy, so every launch runs there. A fleet
        # puts replicas on different chips by placing their params.
        self.device = params_device(params)
        self.num_slots = slots
        # cache hierarchy (PR 16): host_blocks > 0 attaches a host-RAM
        # spill tier of that many blocks under the device pool —
        # preemption and trie eviction demote instead of destroy, and
        # the scheduler swaps demoted blocks back in (prefetched ahead
        # of admission). 0 = off: byte-identical to the pool-only
        # engine. The swap path is ENTIRELY host-side eager copies —
        # it never touches the two compiled step programs.
        if host_blocks < 0:
            raise ValueError(f"host_blocks must be >= 0, got {host_blocks}")
        if persist_cache:
            if snapshot_dir is None:
                raise ValueError(
                    "persist_cache requires ServeEngine(snapshot_dir=...)")
            if not prefix_cache:
                raise ValueError(
                    "persist_cache requires prefix_cache=True (the trie "
                    "is what indexes the persisted blocks)")
            if not host_blocks:
                raise ValueError(
                    "persist_cache requires host_blocks > 0 (restored "
                    "cache contents land in the host tier)")
        if self.fns.cfg.stateful and (prefix_cache or host_blocks):
            # a prefix hit or a swap-in hands a request blocks of keys and
            # values and skips the prefill that would have made its other
            # state: served so, its first token would already be wrong
            raise ValueError(
                "this model's sequences carry state beside their keys and "
                "values in the pool (its "
                f"{' and '.join(self.fns.cfg.state_mixers)} "
                "mixers'), and the prefix cache, the host tier and KV "
                "adoption move blocks of the pool alone: prefix_cache and "
                "host_blocks must stay off (a preempted or migrated request "
                "re-prefills, which rebuilds the state)")
        self.persist_cache = bool(persist_cache)
        self.store = (BlockStore(capacity=host_blocks) if host_blocks
                      else None)
        # observability (PR 14): strictly observe-only. Resolved ONCE
        # here; every emission site guards on ``rec.enabled`` so a
        # disabled recorder costs one attribute check per site
        # (benchmarks/bench_obs.py pins the overhead), and nothing the
        # recorder sees ever feeds a compiled program (the bitwise
        # recorder-on/off parity tests pin that).
        self.rec = recorder if recorder is not None else obs_events.current()
        self.sched = Scheduler(
            slots=slots, num_blocks=num_blocks, block_size=block_size,
            prefill_chunk=prefill_chunk, max_len=self.fns.cfg.max_len,
            max_queue=max_queue, prefix_cache=prefix_cache,
            tenant_quotas=tenant_quotas, drr_quantum=drr_quantum,
            host_store=self.store,
            cache_io=(SimpleNamespace(d2h=self._cache_d2h,
                                      d2h_many=self._cache_d2h_many,
                                      h2d=self._cache_h2d,
                                      h2d_many=self._cache_h2d_many)
                      if self.store is not None else None),
            recorder=self.rec, settle=self._settle)
        if self.fns.lora:
            # the bank is a jit-operand (not a closed-over constant):
            # swapping adapter weights never retraces the two programs
            self.adapters = jax.device_put(
                adapters if adapters is not None
                else init_adapter_bank(self.fns.cfg), self.device)
        elif adapters is not None:
            raise ValueError(
                "ServeEngine(adapters=...) requires cfg.lora_rank")
        else:
            self.adapters = None
        self.pool = paged_cache_pool(self.fns.cfg, slots, self.device)
        self.state = (slot_state(self.fns.cfg, slots, self.device)
                      if self.fns.patterned else None)
        # the routed layers' census summed over launches: every live row's
        # choices, and those that fell to experts this program holds
        self.routed_assignments = {"assignments": 0, "held_assignments": 0}
        self._trash_row = table_row(
            [], self.fns.n_blk, self.sched.pool.trash_block)
        if self.store is not None:
            # warm the d2h/h2d transfer path (the fused gather/scatter
            # programs compile once per pool geometry at their single
            # padded width): a roundtrip through the trash block —
            # scratch by design, and the write-back restores its
            # bytes — so the first REAL swap isn't charged XLA
            # compiles mid-serve
            trash = self.sched.pool.trash_block
            self._cache_h2d(trash, self._cache_d2h(trash))
        self.steps = {"decode": 0, "prefill": 0, "idle": 0}
        # the prefill program's widths this engine launches (a padding
        # row's state row is a slot's, so none is wider than the slots)
        # and the chunks its prefill launches carried
        ladder = (CPU_PREFILL_WIDTHS if jax.default_backend() == "cpu"
                  else PREFILL_WIDTHS)
        self._widths = tuple(w for w in ladder if w <= slots)
        self.prefill_chunks = 0
        # one launch in flight: the slots' pending tokens where the decode
        # program reads them, on the device (row i is slot i's; ``_row_slot``
        # says whose token a row holds, so that a slot whose token only the
        # host knows, a swapped-in continuation's, is set from there); the
        # launch not yet settled; the settled events not yet handed out,
        # with the kind of the launch they came from
        self._pending = jax.device_put(np.zeros((slots,), np.int32),
                                       self.device)
        self._row_slot: list = [None] * slots
        self._inflight: _Launch | None = None
        self._settled: list[Event] = []
        self._settled_kind: str | None = None
        self.launches = 0
        self.overlapped_launches = 0  # dispatched with the last unsettled
        # MoE serving census (observe-only, absorbed by obs/metrics):
        # per-expert token load / overflow counts summed over launches
        # and layers, plus the stall tally of the degrade-to-overflow
        # retry loop (a stalled slot-tick is one discarded sample)
        if self.fns.moe:
            n_e = self.fns.cfg.moe_experts
            self._moe_load = np.zeros((n_e,), np.int64)
            self._moe_overflow = np.zeros((n_e,), np.int64)
            self._moe_stall_slot_ticks = 0
            self._moe_stall_ticks = 0
        # failure hardening (PR 11)
        self.chaos = chaos  # a testing.chaos.FaultSchedule (or None)
        self.burst_factory = burst_factory  # (n, now) -> [Request]
        self.retry_attempts = retry_attempts
        self.retry_base_delay_s = retry_base_delay_s
        self._injected_exc = 0  # pending chaos launch failures
        # every failed launch ATTEMPT (retried-and-recovered ones
        # included) — what the fleet breaker and dtg_serve metrics read
        self.launch_failures = 0
        self._pressure_holds: list[tuple[float, list[int]]] = []
        self._tick = 0
        self._ttft_ewma: float | None = None  # predicted-TTFT shed gate
        self.last_tick_s = 0.0
        self._step_deadline_s = step_deadline_s
        self._watchdog = (Watchdog(name="serve-engine",
                                   recorder=self.rec)
                          if step_deadline_s else None)
        self.snapshot_dir = snapshot_dir
        self._ckpt = None
        self._last_snap = -1
        if snapshot_dir is not None:
            # lazy import: orbax only loads when snapshots are in play
            from distributed_tensorflow_guide_tpu.train.checkpoint import (
                Checkpointer,
            )
            self._ckpt = Checkpointer(snapshot_dir,
                                      max_to_keep=snapshot_keep)

    # ---- intake ----------------------------------------------------------

    def submit(self, req: Request) -> None:
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if prompt.size and int(prompt.max()) >= self.fns.cfg.vocab_size:
            raise ValueError("prompt token out of vocabulary")
        if self.fns.lora:
            if not 0 <= req.adapter <= self.fns.cfg.lora_adapters:
                raise ValueError(
                    f"request {req.rid} adapter {req.adapter} out of "
                    f"range [0, {self.fns.cfg.lora_adapters}]")
        elif req.adapter != 0:
            raise ValueError(
                f"request {req.rid} names adapter {req.adapter} but the "
                "engine config has no lora_rank")
        # predicted-SLO gate: if recent TTFTs already blow this request's
        # TTFT budget, admitting it is a guaranteed miss that would ALSO
        # push every queued request further out — shed at the door
        # instead (retriable; nothing recorded). Queue-depth shedding
        # lives in Scheduler.submit behind max_queue.
        if (req.ttft_deadline_s is not None
                and self._ttft_ewma is not None
                and self._ttft_ewma > req.ttft_deadline_s):
            self.sched.shed += 1
            if self.rec.enabled:
                self.rec.emit(
                    "req.shed", cat="serve", actor="engine",
                    payload={"rid": req.rid, "reason": "ttft",
                             "tenant": int(req.tenant),
                             "ttft_s": self._ttft_ewma},
                    t=float(req.arrival))
            raise EngineOverloaded(
                f"request {req.rid} shed: recent TTFT "
                f"{self._ttft_ewma:.3f}s exceeds its "
                f"{req.ttft_deadline_s:.3f}s deadline — retry later")
        self.sched.submit(dataclasses.replace(
            req, prompt=prompt, rng=np.asarray(req.rng, np.uint32)))
        if self.rec.enabled:
            self.rec.emit(
                "req.submit", cat="serve", actor="engine",
                payload={"rid": req.rid, "tenant": int(req.tenant),
                         "adapter": int(req.adapter),
                         "prompt_len": int(prompt.size),
                         "max_new": int(req.max_new_tokens)},
                t=float(req.arrival))

    def cancel(self, rid: int) -> bool:
        """Client abandon: free the stream's slot+blocks at the next step
        boundary. Returns False for unknown/already-terminal rids."""
        return self.sched.cancel(rid)

    # ---- cache hierarchy io (PR 16) --------------------------------------

    def _cache_d2h(self, block: int) -> list[np.ndarray]:
        """Copy one pool block's rows to host — one numpy array per
        cache-collection leaf (k, v, and the int8 scale rows when
        quantized), in ``jax.tree.leaves`` order.  Routed through the
        batch path so even a single-block spill costs ONE dispatch."""
        return self._cache_d2h_many([block])[0]

    def _cache_d2h_many(self, blocks: list[int]) -> list[list[np.ndarray]]:
        """Copy several pool blocks' rows to host in ONE
        :func:`_pool_gather` dispatch for the whole tree.  The batch is
        padded to a multiple of 8 with trash-block rows (dropped before
        returning) so the gather compiles at ONE width — warmed at
        engine init, never mid-serve.  Rows are copied out of the
        stacked result so the payloads the host store retains don't pin
        the padded buffer."""
        n = len(blocks)
        pad = -(-n // 8) * 8 - n
        trash = self.sched.pool.trash_block
        idx = np.asarray(list(blocks) + [trash] * pad, np.int32)
        stacked = [np.asarray(s) for s in _pool_gather(self.pool, idx)]
        return [[s[j].copy() for s in stacked] for j in range(n)]

    def _cache_h2d(self, block: int, payload: list[np.ndarray]) -> None:
        """Write a host payload into device pool block ``block`` — the
        single-block face of :meth:`_cache_h2d_many`."""
        self._cache_h2d_many([block], [payload])

    def _cache_h2d_many(self, blocks: list[int],
                        payloads: list[list[np.ndarray]]) -> None:
        """Write several host payloads into their device pool blocks in
        ONE :func:`_pool_scatter` dispatch for the whole tree —
        functional updates, so the donated pool the step programs alias
        is never mutated behind XLA's back.  Per-op dispatch overhead
        dominates the eager swap path, which is why the whole tree
        fuses into one program and why the batch is padded to a
        multiple of 8 with writes of the first payload into the trash
        block (scratch by design): one compiled width, warmed at
        engine init — a varying-width batch would reintroduce mid-serve
        compile stalls."""
        n = len(blocks)
        pad = -(-n // 8) * 8 - n
        trash = self.sched.pool.trash_block
        idx = np.asarray(list(blocks) + [trash] * pad, np.int32)
        rows = [np.stack([np.asarray(p[i]) for p in payloads]
                         + [np.asarray(payloads[0][i])] * pad)
                for i in range(len(payloads[0]))]
        self.pool = _pool_scatter(self.pool, idx, rows)

    # ---- fleet tier: stream export / adoption (PR 18) --------------------

    def export_stream(self, rid: int, *, with_kv: bool = True) -> dict:
        """Detach a live stream into a portable migration record for
        another replica's :meth:`adopt_stream`.  ``with_kv=True`` d2h-
        copies the stream's written KV blocks (one fused gather for the
        whole tree) BEFORE the scheduler frees them, so a decode-phase
        stream resumes at the target by swap-in instead of re-prefill;
        ``with_kv=False`` ships the continuation alone (the target
        re-prefills — same stream bitwise either way, by the position-
        derived sampling keys).  The record's ``payload_bytes`` is what
        the fleet charges against the DCN roofline."""
        if with_kv and self.fns.cfg.stateful:
            raise ValueError(
                "export_stream(with_kv=True): this model's sequences carry "
                "state beside their keys and values, which the blocks do "
                "not hold; export with_kv=False (the target re-prefills)")
        keep = self.sched.migratable_blocks(rid) if with_kv else []
        payloads = self._cache_d2h_many(keep) if keep else []
        record = self.sched.detach_stream(rid)
        record["payloads"] = payloads
        record["payload_bytes"] = sum(
            int(a.nbytes) for p in payloads for a in p)
        return record

    def adopt_stream(self, record: dict) -> None:
        """Adopt a migrated stream exported by another replica.  KV
        payloads land in THIS engine's host spill store (the adoption
        landing pad) and the stream resumes by the normal swap-in path
        at its next admission — ``submitted`` is never recounted (the
        scheduler's attach bypasses submit by contract)."""
        if record.get("payloads") and self.fns.cfg.stateful:
            raise ValueError(
                "adopt_stream: KV payloads cannot resume a sequence of "
                "this model (its other state is not in them); export "
                "with_kv=False")
        if record.get("payloads") and self.store is None:
            raise RuntimeError(
                "adopting KV payloads needs ServeEngine(host_blocks>0) "
                "(the adoption landing pad); export with_kv=False to "
                "re-prefill instead")
        self.sched.attach_stream(record)

    # ---- the tick --------------------------------------------------------

    def step(self, now: float = 0.0) -> tuple[list[Event], str]:
        """One engine tick: apply due chaos faults, sweep lifecycle
        (cancellations / deadlines), admit arrived requests, launch (at
        most) one program, then settle the launch BEFORE it: fetch its
        tokens and hand out its events while the device runs this one.
        Returns (events, kind): the events and the kind, in {"prefill",
        "decode"}, of the launch that was settled, after them what the
        sweep ended in this call. A call that launched and had nothing to
        settle (the first after idle) returns ``([], kind)`` of what it
        launched; one with nothing to launch settles what is owed and
        returns its kind; "idle" means nothing was launched and nothing
        was owed. The bench times this call to get per-launch service
        time.

        The tick is one span, ``engine.tick`` (with the stall watch's
        ``gc_full`` and ``compiles`` as it opens: ``dtg.gc`` and
        ``dtg.compile`` spans fall inside whichever phase they strike),
        and its phases five more
        under it (obs/tracing.span: on the profiler's clock whenever a
        session runs, in the recorder when it is enabled): ``schedule``
        up to the plan, ``build`` the launch's host operands,
        ``dispatch`` the jitted calls until they return (``overlapped``:
        the last launch was still unsettled), then ``fetch`` the host
        blocked until the device hands the tokens back and ``apply``
        from filling them in to the lifecycle events. The last two carry
        the ``tick`` (and ``rids``) of the launch they settle, not of the
        call they run in."""
        tick = self._tick
        self._tick += 1
        rec, sd, stalls = self.rec, self.sched, self._stalls
        with span(rec, "engine.tick", cat="serve", tick=tick,
                  gc_full=stalls.gc_full, compiles=stalls.compiles):
            with span(rec, "engine.schedule", cat="serve", tick=tick):
                if rec.enabled:
                    sd.now = now  # timestamps scheduler decisions
                if self.chaos is not None:
                    if rec.enabled:
                        self.chaos.recorder = rec
                        self.chaos.obs_now = now
                    self._apply_chaos(tick, now)
                self._release_pressure(tick)
                swept = [Event(now, *t) for t in sd.sweep(now)]
                if self.store is not None:
                    # prefetch ahead of schedule: queued spilled
                    # continuations' h2d copies land NOW, before this
                    # tick's launch, so a swap-in resume at a later admit
                    # finds its blocks already on device instead of
                    # serializing the copies with it
                    sd.prefetch()
                sd.admit(now)
                kind, arg = sd.plan()
            t0 = time.perf_counter()
            if kind == "idle":
                self._settle(now)
            else:
                self._dispatch(kind, arg, tick, now)
            # the launch before this one predates this call's sweep: its
            # tokens come first, so none follows its request's terminal
            events, settled = self._settled + swept, self._settled_kind
            self._settled, self._settled_kind = [], None
            if settled is not None:
                kind = settled
            if kind == "idle":
                self.last_tick_s = 0.0
                self.steps[kind] += 1
            else:
                self.last_tick_s = time.perf_counter() - t0
            if rec.enabled and swept:
                self._emit_lifecycle(swept, now, tick)
        return events, kind

    def _dispatch(self, kind: str, arg, tick: int, now: float) -> None:
        """Launch what the plan asked for, tell the scheduler at once
        (the tokens' values left open), and only then settle the launch
        before it, which the device finished while this one was built. A
        prefill launch takes the next chunk of as many of the plan's
        waiting prompts as the widest program has rows, oldest first, in
        the narrowest program that holds them. A ``MoEMLP`` model's
        bookkeeping depends on what comes back (an overflowed row keeps
        its token), so its launch is settled at once, and advanced there:
        the same calls, none in flight."""
        rec, sd = self.rec, self.sched
        t0 = time.perf_counter()
        keys, counts = {}, {}
        if kind == PREFILL:
            self._compile_prefill_widths()
            arg = arg[:self._widths[-1]]
            width = next(w for w in self._widths if w >= len(arg))
            program = "prefill_chunk_step"
            held = [sd.slots[i] for i in arg]
            # (a space between them: the profiler's stats end at a comma)
            ids = {"tick": tick, "rids": " ".join(str(s.rid) for s in held)}
            counts = {"chunks": len(arg), "width": width}
            # a row whose prompt ends in this chunk: its sample is the
            # request's first token, where the next decode launch reads it
            place = np.full((width,), self.num_slots, np.int32)
            for r, (i, s) in enumerate(zip(arg, held)):
                if s.chunk_cursor + 1 == sd.prefill_done_chunks(i):
                    place[r] = i
        else:
            width, program = None, "decode_step"
            ids = {"tick": tick}
            keys = self._decode_keys(arg)
        payload = None
        if rec.enabled:
            # launch identity, taken BEFORE the scheduler is told: it
            # frees a slot the moment its request completes
            payload = {"slots": list(arg),
                       "rids": [sd.slots[i].rid for i in arg], "tick": tick}
            if kind == PREFILL:
                payload.update(chunks=[s.chunk_cursor for s in held],
                               width=width)
        with span(rec, "engine.build", cat="serve", kind=kind,
                  rows=len(arg), **ids):
            args = (self._prefill_operands(arg, width) if kind == PREFILL
                    else self._decode_operands(arg))
            fn = self._program(kind, args, width)
        overlapped = int(self._inflight is not None)
        with span(rec, "engine.dispatch", cat="serve", program=program,
                  overlapped=overlapped, **counts, **ids):
            toks, self.pool, *outs = self._launch(
                lambda: fn(*args), tag="serve_" + program)
            if self.fns.patterned:
                self.state, *outs = outs
            # the next decode launch reads these tokens where they are
            if kind == PREFILL:
                self._pending = _place_tokens(self._pending, toks, place)
                for r, i in enumerate(arg):
                    if place[r] == i:
                        self._row_slot[i] = held[r]
            else:
                took = np.zeros((self.num_slots,), bool)
                took[arg] = True
                self._pending = _merge_tokens(self._pending, toks, took)
        self.launches += 1
        self.overlapped_launches += overlapped
        self.steps[kind] += 1
        self.prefill_chunks += counts.get("chunks", 0)
        launch = _Launch(kind, arg, ids, now, t0, (toks, *outs), payload,
                         keys)
        before, self._inflight = self._inflight, launch
        if not self.fns.moe:
            launch.produced = self._advance(launch)
            launch.owed, sd.owed = sd.owed, []
        if before is not None:
            self._fetch_apply(before, now)
        if self.fns.moe:
            self._settle(now)

    def _compile_prefill_widths(self) -> None:
        """Before the engine's first prefill launch, every width of the
        prefill program once (and the small program that places its
        tokens), all rows padding: a width met for the first time later
        would be compiled inside somebody's tick, tens of seconds in which
        no stream gets a token. Engines that share the traced programs and
        the shapes that reach them (``build_step_fns``' memo; the slots,
        the chunk's length, the device) share the compiled ones."""
        shapes = (self.num_slots, self.sched.prefill_chunk, self.device,
                  self._widths)
        if shapes in self.fns.compiled_widths:
            return
        nowhere = np.full((self._widths[-1],), self.num_slots, np.int32)
        for width in self._widths:
            args = self._prefill_operands([], width)
            toks, self.pool, *outs = self._program(PREFILL, args, width)(*args)
            if self.fns.patterned:
                self.state, *outs = outs
            self._pending = _place_tokens(self._pending, toks,
                                          nowhere[:width])
        self.fns.compiled_widths.add(shapes)

    def _program(self, kind: str, args: tuple, width: int | None):
        """The step program of ``kind`` (the prefill one: of ``width``
        rows) as a launch calls it with ``args``: the executable kept for
        it where a compile cache is configured (``program_cache``: a
        later process then loads it and traces nothing), else the jitted
        program itself. Engines that share the traced programs and their
        shapes share the executables."""
        jitted = self.fns.prefill if kind == PREFILL else self.fns.decode
        if program_cache.directory() is None:
            return jitted
        shapes = (kind, width, self.num_slots, self.sched.prefill_chunk,
                  self.device)
        if shapes not in self.fns.programs:
            self.fns.programs[shapes] = program_cache.load_or_compile(
                jitted, args, program=kind, static=self.fns.static,
                device=self.device)
        return self.fns.programs[shapes]

    def _decode_keys(self, ready: list[int]) -> dict:
        """What a decode launch over the ``ready`` slots reads of keys and
        values, for a model with window or latent layers (``{}`` for any
        other): ``live_keys``, the rows' live lengths after the launch's
        write summed (what a full-attention or latent layer, and each layer
        that reads its cache, attends), and with window layers
        ``window_keys``, each length capped at the window (what a window
        layer attends)."""
        window, latent = self.fns.cfg.window, self.fns.cfg.latent
        if window is None and latent is None:
            return {}
        live = [self.sched.slots[i].written + 1 for i in ready]
        if window is None:
            return {"live_keys": sum(live)}
        return {"live_keys": sum(live),
                "window_keys": sum(min(n, window) for n in live)}

    def _advance(self, launch: _Launch, toks=None,
                 overflowed=None) -> list[tuple]:
        """The scheduler's bookkeeping for ``launch``, a slot at a time in
        the launch's order: with ``toks`` None every token's value is left
        open (``Scheduler.fill`` takes it later; ``launch.took`` says where
        in the launch's tokens it will lie). ``overflowed`` (``MoEMLP``
        only, and then ``toks`` is known) flags the slots whose token came
        from a forward that skipped its expert at some layer."""
        sd = self.sched
        produced, stalled = [], 0
        for r, i in enumerate(launch.arg):
            # a prefill launch's tokens lie a row each, a decode launch's
            # a slot each
            at = r if launch.kind == PREFILL else i
            tok = None if toks is None else int(toks[at])
            if launch.kind == PREFILL:
                events = sd.apply_prefill(i, tok)
            elif overflowed is not None and overflowed[i]:
                # degrade-to-overflow: discard the token and leave
                # pending/written untouched, so the SAME token retries
                # next tick (cache rewrites are idempotent; dispatch fills
                # in slot order, so the lowest contending slot always
                # advances). A hot expert costs goodput, never a dropped
                # or corrupted token. The device's row took the discarded
                # sample: the host sets it again.
                stalled += 1
                self._row_slot[i] = None
                continue
            else:
                events = sd.apply_decode(i, tok)
            produced.extend(events)
            launch.took.extend([at] * len(events))
        if stalled:
            self._moe_stall_slot_ticks += stalled
            self._moe_stall_ticks += 1
        return produced

    def _settle(self, now: float | None = None) -> None:
        """Fetch and apply the launch in flight, if there is one; its
        events wait in ``_settled`` for the next hand-out."""
        launch, self._inflight = self._inflight, None
        if launch is not None:
            self._fetch_apply(launch, now)

    def settle(self) -> list[Event]:
        """Fetch the tokens the device still owes and fill them in: after
        it every emitted token has its value and nothing is in flight. A
        no-op with nothing owed. Returns the events not yet handed out
        (what the next :meth:`step` would have returned first). The
        engine settles by itself before anything that reads a token's
        value: :meth:`completions`, :meth:`health`, a snapshot, a stream's
        export, a preemption, :meth:`close`."""
        self._settle()
        events, self._settled, self._settled_kind = self._settled, [], None
        return events

    def _fetch_apply(self, launch: _Launch, now: float | None) -> None:
        """Settle ``launch``: wait for what it hands back, fill the
        values in (or, where nothing was advanced yet, advance with them)
        and build its events, timed ``now`` (the call that hands them
        out; the dispatching call's where there is none)."""
        rec, sd, ids = self.rec, self.sched, launch.ids
        now = launch.now if now is None else now
        toks, *outs = launch.outs
        routed = {}
        with span(rec, "engine.fetch", cat="serve", **ids):
            toks = np.asarray(toks)
            if self.fns.patterned:
                cfg = self.fns.cfg
                routed = _routed_counters(
                    np.asarray(outs[0]), cfg.routed_first, cfg.routed_count)
                for k in self.routed_assignments:
                    self.routed_assignments[k] += routed.get(k, 0)
            elif outs:  # ([overflowed slots,] expert load, overflow)
                outs = [np.asarray(x) for x in outs]
                self._moe_load += outs[-2].astype(np.int64)
                self._moe_overflow += outs[-1].astype(np.int64)
        with span(rec, "engine.apply", cat="serve", **ids, **routed,
                  **launch.keys):
            if launch.produced is None:
                produced = self._advance(
                    launch, toks, outs[0] if len(outs) == 3 else None)
            else:
                values = toks.tolist()
                values = [values[at] for at in launch.took]
                sd.fill(launch.owed, values)
                produced = [(rid, value, first, done)
                            for (rid, _, first, done), value
                            in zip(launch.produced, values)]
            events = [Event(now, *ev) for ev in produced]
            if launch.payload is not None:
                launch.payload["dur_s"] = time.perf_counter() - launch.t0
                rec.emit(f"{launch.kind}.launch", cat="serve",
                         actor="engine", payload=launch.payload,
                         t=launch.now)
            for e in events:
                if e.first:
                    arrival = sd.meta.get(e.rid, (now, None, None))[0]
                    ttft = max(0.0, now - arrival)
                    if np.isfinite(ttft):
                        self._ttft_ewma = (
                            ttft if self._ttft_ewma is None
                            else 0.8 * self._ttft_ewma + 0.2 * ttft)
            if rec.enabled and events:
                self._emit_lifecycle(events, now, ids["tick"])
        self._settled.extend(events)
        self._settled_kind = launch.kind

    def _emit_lifecycle(self, events: list[Event], now: float,
                        tick: int) -> None:
        """Map the tick's swept/produced events onto recorder instants:
        ``req.first_token`` / ``req.done`` for streams, ``req.cancelled``
        / ``req.expired`` for sweep casualties."""
        rec = self.rec
        for e in events:
            if e.status != "ok":
                rec.emit(f"req.{e.status}", cat="serve", actor="engine",
                         payload={"rid": e.rid, "tick": tick}, t=now)
                continue
            if e.first:
                payload = {"rid": e.rid, "tick": tick}
                arrival = self.sched.meta.get(e.rid, (now, None, None))[0]
                ttft = now - arrival
                if np.isfinite(ttft):
                    payload["ttft_s"] = float(max(0.0, ttft))
                rec.emit("req.first_token", cat="serve", actor="engine",
                         payload=payload, t=now)
            if e.done:
                rec.emit("req.done", cat="serve", actor="engine",
                         payload={"rid": e.rid, "tick": tick,
                                  "tokens": len(self.sched.emitted.get(
                                      e.rid, []))},
                         t=now)

    def _launch(self, fn, tag: str):
        """One guarded program launch: a per-attempt watchdog deadline
        (a hung compiled step becomes :class:`WatchdogTimeout`, not a
        silent stall) wrapped in the shared ``retry_with_backoff`` — a
        transient failure re-runs the SAME tick bitwise, because every
        launch input is rebuilt from host state and the sampling keys
        are position-derived. Injected chaos failures fire BEFORE the
        program runs (the pool is untouched) and retry on every backend.
        A real failure that lands mid-launch on a donating backend is NOT
        retried: the pool was donated, so a second attempt could only
        fail on the deleted buffer and bury the first error under its
        own. It propagates as it is; that path recovers via snapshot
        restore, as docs/serving.md spells out."""

        def attempt():
            try:
                if self._injected_exc:
                    self._injected_exc -= 1
                    raise ChaosInjectedError(
                        f"chaos: injected serve step exception ({tag})")
                wd = self._watchdog
                if wd is None:
                    return fn()
                wd.arm(tag, self._step_deadline_s)
                try:
                    return fn()
                except KeyboardInterrupt:
                    wd.check()  # trip becomes the clean, retriable error
                    raise
                finally:
                    wd.disarm()
            except Exception:
                self.launch_failures += 1
                raise

        return retry_with_backoff(
            attempt, attempts=self.retry_attempts,
            base_delay_s=self.retry_base_delay_s, max_delay_s=1.0,
            retry_on=((ChaosInjectedError,) if self.fns.donates_pool
                      else (RuntimeError, OSError)),
            what=tag)

    def _prefill_operands(self, rows: list[int], width: int) -> tuple:
        """The next chunk of each of the slots ``rows``' prompts as the
        arguments of the prefill program of ``width`` rows. The rows past
        them are padding: no valid position, an all-trash table, and (a
        model with state leaves) the state row of a slot that no real row
        of the launch holds, which the program hands back as it was; the
        rows' updates run in order, so a row given twice would not be."""
        sd = self.sched
        CH = sd.prefill_chunk
        tables = np.tile(self._trash_row, (width, 1))
        start, valid = (np.zeros((width,), np.int32) for _ in range(2))
        chunk = np.zeros((width, CH), np.int32)
        keys = np.zeros((width, 2), np.uint32)
        adapter_ids = np.zeros((width,), np.int32)
        for r, i in enumerate(rows):
            s = sd.slots[i]
            start[r] = s.chunk_cursor * CH
            valid[r] = min(CH, len(s.prompt) - start[r])
            chunk[r, :valid[r]] = s.prompt[start[r]:start[r] + valid[r]]
            tables[r] = table_row(s.blocks, self.fns.n_blk,
                                  sd.pool.trash_block)
            keys[r] = s.rng
            adapter_ids[r] = s.adapter
        # host operands go in as numpy: the launch moves them straight to
        # the device the committed params and pool are on
        if self.fns.patterned:
            spare = [i for i in range(self.num_slots) if i not in rows]
            state_rows = np.asarray(
                list(rows) + spare[:width - len(rows)], np.int32)
            return (self.params, self.pool, self.state, tables, start,
                    chunk, valid, keys, state_rows)
        args = (self.params, self.pool, tables, start, chunk, valid, keys)
        if self.fns.lora:
            args += (self.adapters, adapter_ids)
        return args

    def _decode_operands(self, ready: list[int]) -> tuple:
        """One decode step over the ``ready`` slots as the decode
        program's arguments; every other row reads the trash block. The
        tokens go in where they are, on the device (``_pending``: not
        donated, so a retried launch reads the same); a row whose slot's
        token only the host knows is set from there first."""
        S, n_blk = self.num_slots, self.fns.n_blk
        tables = np.tile(self._trash_row, (S, 1))
        written = np.zeros((S,), np.int32)
        keys = np.zeros((S, 2), np.uint32)
        adapter_ids = np.zeros((S,), np.int32)
        known, stale = np.zeros((S,), np.int32), np.zeros((S,), bool)
        for i in ready:
            s = self.sched.slots[i]
            tables[i] = table_row(s.blocks, n_blk,
                                  self.sched.pool.trash_block)
            written[i] = s.written
            keys[i] = s.rng
            adapter_ids[i] = s.adapter
            if self._row_slot[i] is not s:
                self._row_slot[i] = s
                known[i], stale[i] = s.pending, True
        if stale.any():
            # (on the device first: the merge then takes what it takes
            # after a decode launch, and compiles nothing new mid-serve)
            self._pending = _merge_tokens(
                self._pending, jax.device_put(known, self.device), stale)
        last_tok = self._pending
        if self.fns.patterned:
            return (self.params, self.pool, self.state, tables, written,
                    last_tok, keys)
        args = (self.params, self.pool, tables, written, last_tok, keys)
        if self.fns.lora:
            args += (self.adapters, adapter_ids)
        return args

    # ---- chaos application (testing.chaos serve kinds) -------------------

    def _apply_chaos(self, tick: int, now: float) -> None:
        from distributed_tensorflow_guide_tpu.testing.chaos import (
            corrupt_checkpoint,
        )
        for f in self.chaos.take_serve(tick):
            if f.kind == "serve_step_exception":
                self._injected_exc += 1
            elif f.kind == "client_abandon":
                rid = self._abandon_target(int(f.param))
                if rid is not None:
                    self.cancel(rid)
            elif f.kind == "arrival_burst":
                if self.burst_factory is None:
                    raise ValueError(
                        "arrival_burst fault needs "
                        "ServeEngine(burst_factory=...)")
                # a tenant-targeted burst exercises fair-share admission:
                # legacy 2-arg factories still work for tenantless faults
                reqs = (self.burst_factory(int(f.param), now)
                        if f.tenant is None
                        else self.burst_factory(int(f.param), now,
                                                int(f.tenant)))
                for req in reqs:
                    try:
                        self.submit(req)
                    except EngineOverloaded:
                        pass  # the gate shedding the burst IS the scenario
            elif f.kind == "pool_pressure":
                self._grab_pressure(tick, int(f.param))
            else:  # snapshot_truncate / snapshot_corrupt
                if self.snapshot_dir is None:
                    raise ValueError(
                        f"{f.kind} fault needs ServeEngine("
                        "snapshot_dir=...)")
                if self._ckpt is not None:
                    self._ckpt.wait()  # commit pending async saves first
                try:
                    corrupt_checkpoint(
                        self.snapshot_dir,
                        mode=("truncate" if f.kind == "snapshot_truncate"
                              else "flip"))
                except FileNotFoundError:
                    pass  # no committed snapshot yet — nothing to damage

    def _abandon_target(self, idx: int) -> int | None:
        live = sorted(
            {s.rid for s in self.sched.slots if s is not None}
            | {r.rid for r in self.sched.queue})
        if not live:
            return None
        return live[idx % len(live)]

    def _grab_pressure(self, tick: int, nblocks: int) -> None:
        # a co-tenant spike: blocks vanish from the pool for a few ticks
        # under the reserved chaos owner, forcing eviction/re-prefill on
        # residents — released by _release_pressure (or at run() exit)
        pool = self.sched.pool
        n = min(nblocks, pool.free_blocks)
        if n <= 0:
            return
        blocks = pool.alloc(_CHAOS_RID, n)
        if blocks:
            self._pressure_holds.append(
                (tick + _PRESSURE_HOLD_TICKS, blocks))

    def _release_pressure(self, tick: float) -> None:
        keep = []
        for release_at, blocks in self._pressure_holds:
            if tick >= release_at:
                self.sched.pool.free(_CHAOS_RID, blocks)
            else:
                keep.append((release_at, blocks))
        self._pressure_holds = keep

    # ---- drain -----------------------------------------------------------

    def run(self, max_ticks: int | None = None) -> list[Event]:
        """Drain all submitted work ignoring arrival times (tick clock).
        The load bench drives :meth:`step` itself with a virtual clock
        instead."""
        events: list[Event] = []
        ticks = 0
        while self.sched.has_queued or self.sched.has_resident:
            evs, kind = self.step(now=float("inf"))
            events.extend(evs)
            if kind == "idle":
                if self._pressure_holds:
                    continue  # chaos holds blocks; they release by tick
                raise RuntimeError(
                    "engine deadlock: work queued but nothing schedulable")
            ticks += 1
            if max_ticks is not None and ticks >= max_ticks:
                break
        events.extend(self.settle())  # a bounded run leaves nothing owed
        self._release_pressure(float("inf"))
        return events

    def completions(self) -> dict[int, list[int]]:
        """rid -> every token emitted so far (complete or not)."""
        self._settle()
        return {rid: list(toks)
                for rid, toks in self.sched.emitted.items()}

    def live_blocks(self) -> int:
        """Blocks currently owned by resident requests — what the paged
        byte model charges a decode step for (vs. max_len always)."""
        return self.sched.pool.live_blocks()

    def health(self) -> dict:
        """Engine health counters — what the CLI/examples surface so a
        degraded engine is observable, not silent. ``launches`` counts
        the programs dispatched and ``overlapped_launches`` those
        dispatched while the launch before was still unsettled: how often
        the device had its next program before the host asked for the
        last one's tokens."""
        self._settle()  # the routing sums are of settled launches
        sd = self.sched
        return {
            "resident": sum(s is not None for s in sd.slots),
            "queued": len(sd.queue),
            "completed": len(sd.done),
            "shed": sd.shed,
            "cancelled": sd.cancelled,
            "expired": sd.expired,
            "preemptions": sd.preemptions,
            "live_blocks": sd.pool.live_blocks(),
            "pool_bytes": _tree_bytes(self.pool),
            **_state_bytes(self.state),
            "routed": dict(self.routed_assignments),
            "prefix_hit_tokens": sd.prefix_hit_tokens,
            "prefill_tokens_saved": sd.prefill_tokens_saved,
            "prefix_evictions": sd.prefix_evictions,
            "prefix_nodes": sd.prefix.size if sd.prefix is not None else 0,
            "spill_out_blocks": sd.spill_out_blocks,
            "spill_in_blocks": sd.spill_in_blocks,
            "spill_d2h_bytes": sd.spill_d2h_bytes,
            "spill_h2d_bytes": sd.spill_h2d_bytes,
            "spill_prefetched_blocks": sd.spill_prefetched_blocks,
            "spill_resumes": sd.spill_resumes,
            "swapin_tokens_saved": sd.swapin_tokens_saved,
            "migrated_out": sd.migrated_out,
            "migrated_in": sd.migrated_in,
            "host_blocks": (self.store.live_blocks()
                            if self.store is not None else 0),
            "host_bytes": (self.store.bytes_stored()
                           if self.store is not None else 0),
            "tenants": {t: dict(c) for t, c in sorted(sd.tenants.items())},
            "last_tick_s": self.last_tick_s,
            "ticks": self._tick,
            "launch_failures": self.launch_failures,
            "launches": self.launches,
            "overlapped_launches": self.overlapped_launches,
            "prefill_launches": self.steps[PREFILL],
            "prefill_chunks": self.prefill_chunks,
            **({"moe": {
                "expert_load": [int(x) for x in self._moe_load],
                "expert_overflow": [int(x) for x in self._moe_overflow],
                "stall_slot_ticks": int(self._moe_stall_slot_ticks),
                "stall_ticks": int(self._moe_stall_ticks),
            }} if self.fns.moe else {}),
        }

    # ---- snapshot / restore ----------------------------------------------

    def save_snapshot(self, *, async_: bool = False) -> int | None:
        """Serialize ALL host-side serving state (the scheduler's
        continuation view of every live request, emitted tokens,
        terminal statuses, counters) through PR 5's manifested /
        CRC-verified checkpoint path. One uint8 blob: the state is a
        dynamic Python structure, so it rides as JSON bytes and the
        manifest's size+CRC checks cover it (``snapshot_truncate`` /
        ``snapshot_corrupt`` are both caught at restore). The device
        pool is NOT saved — restore re-prefills residents from their
        recorded positions, which PR 10's position-derived keys make
        bitwise-safe. Returns the snapshot label, or None if the save
        was skipped."""
        if self._ckpt is None:
            raise ValueError("ServeEngine(snapshot_dir=...) not configured")
        state = {"sched": self.sched.snapshot_state(),
                 "tick": self._tick,
                 "steps": dict(self.steps),
                 "prefill_chunks": self.prefill_chunks}
        blob = np.frombuffer(json.dumps(state).encode("utf-8"),
                             dtype=np.uint8).copy()
        label = max(self._tick, self._last_snap + 1)
        if not self._ckpt.save(label, {"blob": blob}, force=True,
                               async_=async_):
            return None
        self._last_snap = label
        if self.persist_cache:
            self._save_cache_contents(label)
        if self.rec.enabled:
            self.rec.emit(
                "snapshot.save", cat="serve", actor="engine",
                payload={"label": int(label),
                         "requests": len(state["sched"]["requests"]),
                         "async": bool(async_)})
        return label

    def _cache_file(self, label: int) -> str:
        return os.path.join(str(self.snapshot_dir),
                            f"cache_{int(label)}.npz")

    def _save_cache_contents(self, label: int) -> int:
        """Persist the prefix trie's PAYLOADS (device-resident blocks
        d2h'd, spilled blocks straight from the host tier) next to
        snapshot ``label`` as one npz + a CRC sidecar — the warm-restart
        path: a restored engine swallows these into the host tier and
        re-prefills ZERO cached-prefix tokens.  Returns the number of
        nodes written."""
        sd = self.sched
        nodes = []
        arrays = {}
        for j, (adapter, path, node) in enumerate(sd.prefix.walk()):
            payload = (self._cache_d2h(node.block)
                       if node.block is not None
                       else sd.store.get(node.host))
            nodes.append({"adapter": int(adapter),
                          "path": [int(t) for t in path]})
            for k, a in enumerate(payload):
                arrays[f"n{j}_l{k}"] = np.asarray(a)
        sig = [[list(leaf.shape[1:]), str(leaf.dtype)]
               for leaf in jax.tree.leaves(self.pool)]
        meta = json.dumps({"version": 1, "label": int(label),
                           "leaves": sig, "nodes": nodes})
        path = self._cache_file(label)
        buf = _io.BytesIO()
        np.savez(buf, meta=np.frombuffer(meta.encode("utf-8"), np.uint8),
                 **arrays)
        raw = buf.getvalue()
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(raw)
        os.replace(tmp, path)
        with open(path[:-4] + ".crc", "w") as f:
            f.write(str(zlib.crc32(raw)))
        # trim cache files alongside the checkpointer's max_to_keep
        keep = {self._cache_file(s) for s in self._ckpt.all_steps()}
        for old in glob.glob(os.path.join(str(self.snapshot_dir),
                                          "cache_*.npz")):
            if old not in keep:
                for p in (old, old[:-4] + ".crc"):
                    try:
                        os.remove(p)
                    except OSError:
                        pass
        if self.rec.enabled:
            self.rec.emit("snapshot.cache_save", cat="serve",
                          actor="engine",
                          payload={"label": int(label),
                                   "nodes": len(nodes),
                                   "bytes": len(raw)})
        return len(nodes)

    def _restore_cache_contents(self, label: int) -> int:
        """Warm-restore the cache file for snapshot ``label`` into the
        HOST tier: every node re-enters the trie as a spilled entry
        (zero device blocks consumed) and promotes on demand when a
        claim wants it.  Any failure — missing file, CRC mismatch,
        signature drift, truncation — falls back to a cold cache (the
        continuations simply re-prefill; never a wrong token).  Returns
        the number of nodes restored."""
        sd = self.sched
        path = self._cache_file(label)
        try:
            with open(path, "rb") as f:
                raw = f.read()
            with open(path[:-4] + ".crc") as f:
                want = int(f.read().strip())
            if zlib.crc32(raw) != want:
                raise ValueError("cache file CRC mismatch")
            data = np.load(_io.BytesIO(raw))
            meta = json.loads(bytes(data["meta"]).decode("utf-8"))
            sig = [[list(leaf.shape[1:]), str(leaf.dtype)]
                   for leaf in jax.tree.leaves(self.pool)]
            if meta.get("version") != 1 or meta["leaves"] != sig:
                raise ValueError("cache file leaf signature mismatch")
            restored = 0
            for j, nd in enumerate(meta["nodes"]):
                payload = [np.asarray(data[f"n{j}_l{k}"])
                           for k in range(len(sig))]
                for a, (shape, dtype) in zip(payload, sig):
                    if list(a.shape) != shape or str(a.dtype) != dtype:
                        raise ValueError(
                            "cache file node payload shape mismatch")
                h = sd.store.put(CACHE_RID, payload)
                if h is None:
                    break  # host tier full — keep what fits
                if sd.prefix.insert_spilled(nd["path"], h,
                                            adapter=int(nd["adapter"])):
                    restored += 1
                else:
                    sd.store.free(CACHE_RID, [h])
        except Exception as e:
            if self.rec.enabled:
                self.rec.emit("snapshot.cache_restore_miss", cat="serve",
                              actor="engine",
                              payload={"label": int(label),
                                       "error": str(e)})
            return 0
        if self.rec.enabled:
            self.rec.emit("snapshot.cache_restore", cat="serve",
                          actor="engine",
                          payload={"label": int(label),
                                   "nodes": restored})
        return restored

    def restore_latest_snapshot(self) -> int | None:
        """Restore the newest VALID snapshot (the PR-5 ladder: a
        truncated or CRC-corrupt snapshot is skipped, falling back to
        the next older one) into THIS engine, which must be fresh. The
        pool stays zeroed; every formerly-resident request re-enters as
        a queued continuation and re-prefills through normal admission,
        so each stream continues bitwise identical to an uninterrupted
        run. Returns the restored label, or None when no valid snapshot
        exists."""
        if self._ckpt is None:
            raise ValueError("ServeEngine(snapshot_dir=...) not configured")
        self._settle()
        got = self._ckpt.restore_latest_valid(None)
        if got is None:
            if self.rec.enabled:
                self.rec.emit("snapshot.restore_miss", cat="serve",
                              actor="engine", payload={})
            return None
        tree, label = got
        state = json.loads(
            np.asarray(tree["blob"], np.uint8).tobytes().decode("utf-8"))
        self.sched.restore_state(state["sched"])
        if self.persist_cache:
            # warm the trie BEFORE the first admit so every restored
            # continuation routes through the prefix-claim path and
            # re-prefills only its suffix (the fix-of-opportunity:
            # restore cost scales with suffix length, not prompt length)
            self._restore_cache_contents(label)
        self._tick = int(state["tick"])
        for k, v in state["steps"].items():
            self.steps[k] = int(v)
        self.prefill_chunks = int(state.get("prefill_chunks", 0))
        self._last_snap = label
        if self.rec.enabled:
            self.rec.emit(
                "snapshot.restore", cat="serve", actor="engine",
                payload={"label": int(label),
                         "requests": len(state["sched"]["requests"])})
        return label

    def close(self) -> None:
        """Release background resources (watchdog thread, checkpointer)
        and drop the prefix cache's block references — device AND host
        tier — plus any banked spill records, so the joint
        ``Scheduler.check_leaks()`` audits clean after shutdown."""
        self._settle()
        self.sched.release_prefix_cache()
        if self.store is not None:
            self.sched.release_spill_store()
        if self._watchdog is not None:
            self._watchdog.close()
        if self._ckpt is not None:
            self._ckpt.close()


# ---- program contracts (analysis/) ------------------------------------------


def lint_contracts():
    """Contracts for the serving entry programs (base decode/prefill
    pair plus the multi-LoRA decode variant).

    Collective-free (strict empty census: the engine is pure SPMD under
    DP/TP sharding — a stray psum would deadlock a replicated server),
    host-callback-free, pool donated in ``alias`` mode (the pool is
    state->state: every donated leaf must come back out, which is the
    in-place-update guarantee; this is the serving analogue of the
    one-shot cache's scratch donation — the ISSUE's "scratch-donated
    pool" — expressed for a buffer the host threads between ticks), and
    a hard ceiling on the largest f32 intermediate that sits BELOW the
    size of a full-``max_len`` f32 score tensor — the lint fails if
    anyone reintroduces dense (slots, heads, chunk, max_len) attention
    scores into the compiled serve path."""
    from distributed_tensorflow_guide_tpu.analysis.contracts import (
        CostPin,
        CostSpec,
        DonationSpec,
        ProgramContract,
    )

    # fixture geometry: chosen so every legitimate f32 intermediate
    # (largest: one updated pool leaf, num_blocks*heads*block*head_dim =
    # 5*2*8*8 = 640 elems) fits under the cap while a dense f32 score
    # tensor (decode: slots*heads*1*max_len = 2048; prefill chunk:
    # 1*heads*chunk*max_len = 4096) would blow through it
    S, NB, BS, CH, MAXLEN = 4, 5, 8, 8, 256
    F32_CAP = 1024

    def _build(kind):
        def _b():
            from distributed_tensorflow_guide_tpu.analysis.fixtures import (
                tiny_lm_cfg,
            )

            lora = kind == "decode_lora"
            cfg = dataclasses.replace(
                tiny_lm_cfg(vocab_size=32, max_len=MAXLEN),
                decode_impl="pallas",
                **({"lora_rank": 2, "lora_adapters": 2} if lora else {}),
                **({"moe_experts": 4, "moe_capacity": 2}
                   if "moe" in kind else {}),
                **({"weight_dtype": "int8"}
                   if kind in ("decode_wq8", "decode_moe_wq8")
                   else {"weight_dtype": "fp8"} if kind == "decode_wqfp8"
                   else {}))
            fns = build_step_fns(cfg, slots=S, num_blocks=NB,
                                 block_size=BS, prefill_chunk=CH)
            variables = jax.eval_shape(
                lambda p: fns.model.init(
                    jax.random.PRNGKey(0), p,
                    jnp.zeros((S,), jnp.int32),
                    block_tables=jnp.zeros((S, fns.n_blk), jnp.int32)),
                jax.ShapeDtypeStruct((S, 1), "int32"))
            params = variables["params"]
            pool = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                paged_cache_shapes(fns.cfg, S))
            i32 = "int32"
            if kind.startswith("decode"):
                args = (params, pool,
                        jax.ShapeDtypeStruct((S, fns.n_blk), i32),
                        jax.ShapeDtypeStruct((S,), i32),
                        jax.ShapeDtypeStruct((S,), i32),
                        jax.ShapeDtypeStruct((S, 2), "uint32"))
                if lora:
                    adapters = jax.tree.map(
                        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                        variables["adapters"])
                    args += (adapters, jax.ShapeDtypeStruct((S,), i32))
                return fns.decode, args
            args = (params, pool,
                    jax.ShapeDtypeStruct((1, fns.n_blk), i32),
                    jax.ShapeDtypeStruct((1,), i32),
                    jax.ShapeDtypeStruct((1, CH), i32),
                    jax.ShapeDtypeStruct((1,), i32),
                    jax.ShapeDtypeStruct((1, 2), "uint32"))
            return fns.prefill, args

        return _b

    common = dict(
        policy="f32",
        collectives={},  # strict: the serve programs are collective-free
        max_f32_intermediate_elems=F32_CAP,
        donation=DonationSpec(argnums=(1,), mode="alias"),
        sources=("distributed_tensorflow_guide_tpu.serve.engine",
                 "distributed_tensorflow_guide_tpu.serve.paged_cache",
                 "distributed_tensorflow_guide_tpu.models.transformer"),
    )

    # every quantized kernel elem in the fixture model: per layer
    # qkv 768 + proj 256 + up 512 + down 512 = 2048, x 2 layers, plus
    # lm_head 16*32 = 512 -> 4608 elems; int8 storage saves 3 bytes on
    # each one per decode step (the narrow-origin matmul read)
    WQ8_SAVED_BYTES = 3 * 4608

    def _wq8_hbm_read_expect():
        """The f32 sibling's derived read bytes minus the weight-only
        savings — pinning the wq8 program AGAINST its own f32 trace, so
        the pin can only pass if quantization removed exactly the kernel
        bytes and changed nothing else about the program's traffic."""
        import jax.numpy as _jnp

        from distributed_tensorflow_guide_tpu.analysis import (
            cost as cost_mod,
            rules as rules_mod,
        )

        fn, args = _build("decode")()
        jaxpr = jax.make_jaxpr(fn)(*args)
        traced = rules_mod.TracedProgram(
            name="serve_decode_step", jaxpr=jaxpr,
            arg_leaf_avals=[
                [jax.ShapeDtypeStruct(_jnp.shape(x), _jnp.result_type(x))
                 for x in jax.tree.leaves(a)] for a in args])
        f32_vec = cost_mod.program_cost(traced, sibling)
        return f32_vec.hbm_bytes_read - WQ8_SAVED_BYTES

    # the MoE fixture's quantized kernel elems: per layer qkv 768 +
    # proj 256 + expert banks w_in 4*16*32 = 2048 + w_out 4*32*16 = 2048
    # (the routed FFN replaces MLP up/down; the f32 router is exempt),
    # x 2 layers, plus lm_head 512 -> 10752; int8 storage saves 3 bytes
    # per elem on the decode read — the ~4x cold-bank diet, byte-exact
    MOE_WQ8_SAVED_BYTES = 3 * 10752

    def _moe_wq8_hbm_read_expect():
        """The f32 MoE sibling's derived read bytes minus the weight-only
        savings — the serve_decode_step_wq8 trace-and-subtract discipline
        applied to the expert banks, so the pin only passes if
        quantization removed exactly the kernel+bank bytes and changed
        nothing else about the MoE program's traffic."""
        import jax.numpy as _jnp

        from distributed_tensorflow_guide_tpu.analysis import (
            cost as cost_mod,
            rules as rules_mod,
        )

        fn, args = _build("decode_moe")()
        jaxpr = jax.make_jaxpr(fn)(*args)
        traced = rules_mod.TracedProgram(
            name="serve_decode_step_moe", jaxpr=jaxpr,
            arg_leaf_avals=[
                [jax.ShapeDtypeStruct(_jnp.shape(x), _jnp.result_type(x))
                 for x in jax.tree.leaves(a)] for a in args])
        f32_vec = cost_mod.program_cost(traced, moe_sibling)
        return f32_vec.hbm_bytes_read - MOE_WQ8_SAVED_BYTES

    moe_sibling = ProgramContract(
        name="serve_decode_step_moe",
        build=_build("decode_moe"),
        # the MoE pair carries the expert banks (2 layers x 4 experts x
        # (16*32 + 32*16) f32 = 16 KiB of extra resident params) on top
        # of the shared pool band — its own ceiling, same discipline
        cost=CostSpec(max_peak_live_bytes=131072),
        notes="expert-parallel decode: router dispatch + fixed-capacity "
              "expert contraction INSIDE the step; per-slot overflow "
              "flags drive the engine's stall-and-retry (degrade, never "
              "drop); idle slots masked out of capacity",
        **common)

    sibling = ProgramContract(
        name="serve_decode_step",
        build=_build("decode"),
        # one 96KiB ceiling across the serve programs: the aliased
        # pool keeps all three in the 75-91KiB band, and a dead pool
        # donation would blow straight through it
        cost=CostSpec(max_peak_live_bytes=98304),
        notes="fixed-slot paged decode: pool aliased in place, no "
              "full-max_len f32 score tensor",
        **common)
    return [
        sibling,
        ProgramContract(
            name="serve_decode_step_wq8",
            build=_build("decode_wq8"),
            quantized_matmuls=True,
            cost=CostSpec(
                pins=(CostPin(
                    "hbm_bytes_read", _wq8_hbm_read_expect,
                    note="f32 decode read bytes minus 3 B x 4608 "
                         "quantized kernel elems"),),
                max_peak_live_bytes=98304),
            notes="weight-only int8 decode: same program as "
                  "serve_decode_step with every projection kernel "
                  "stored int8 + f32 column scales, dequant fused into "
                  "the matmul (no f32 weight copy under the f32 cap)",
            **common),
        ProgramContract(
            name="serve_decode_step_wqfp8",
            build=_build("decode_wqfp8"),
            # NOT fp8_matmuls: the e4m3 kernels widen through a separate
            # convert eqn before the dot, so every contraction sees f32
            # operands (the weight-only discipline) — there is no fp8 dot
            # for the gate to pass. The pin reuses the int8 expect: fp8
            # is the same 1 byte/elem storage, so the saved read bytes
            # are identical (3 B x 4608 kernel elems vs the f32 sibling).
            cost=CostSpec(
                pins=(CostPin(
                    "hbm_bytes_read", _wq8_hbm_read_expect,
                    note="f32 decode read bytes minus 3 B x 4608 "
                         "fp8-stored kernel elems (same byte diet as "
                         "int8)"),),
                max_peak_live_bytes=98304),
            notes="weight-only fp8 decode: e4m3 projection kernels + f32 "
                  "column scales, dequant fused into the matmul; relative "
                  "(mantissa) error instead of int8's absolute grid",
            **common),
        ProgramContract(
            name="serve_prefill_chunk_step",
            build=_build("prefill"),
            cost=CostSpec(max_peak_live_bytes=98304),
            notes="chunked prefill through the same attention path, at "
                  "the narrowest of its widths (one row)",
            **common),
        ProgramContract(
            name="serve_decode_step_lora",
            build=_build("decode_lora"),
            cost=CostSpec(max_peak_live_bytes=98304),
            notes="multi-adapter decode: gathered low-rank deltas stay "
                  "collective-free and under the f32 intermediate cap",
            **common),
        moe_sibling,
        ProgramContract(
            name="serve_decode_step_moe_wq8",
            build=_build("decode_moe_wq8"),
            quantized_matmuls=True,
            cost=CostSpec(
                pins=(CostPin(
                    "hbm_bytes_read", _moe_wq8_hbm_read_expect,
                    note="f32 MoE decode read bytes minus 3 B x 10752 "
                         "quantized kernel+bank elems — the cold expert "
                         "bank pays the same fused-dequant diet as the "
                         "dense projections"),),
                max_peak_live_bytes=131072),
            notes="weight-only int8 MoE decode: per-expert qkernel+scale "
                  "banks, dequant fused AFTER the expert gather "
                  "(wq_bank_matmul); same program shape as "
                  "serve_decode_step_moe",
            **common),
        ProgramContract(
            name="serve_prefill_chunk_step_moe",
            build=_build("prefill_moe"),
            cost=CostSpec(max_peak_live_bytes=131072),
            notes="MoE chunked prefill, one row: the dispatch buffer "
                  "widens to the rows x the chunk length (dropless by "
                  "construction — a prefill token can never overflow), "
                  "pad rows masked out of the census",
            **common),
    ]
