"""Both drivers end to end at a tiny size on the CPU: the command's own
``main`` with the look for a chip skipped here in the test (the command has
no option for it), the last line's shape, traffic that repeats from a seed,
and the plain reference against the package."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.yardstick import tiny
from yardstick import harness, weights
from yardstick import run as command
from yardstick.generators import request_mix, token_batches
from yardstick.reference import gpt2

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("workload,metric", [
    (tiny.TRAIN, "train_tokens_per_s"), (tiny.SERVE, "serve_tokens_per_s")])
def test_a_whole_run_prints_the_contracts_line(tiny_cells, capsys, workload,
                                               metric):
    rc = command.main(["--workload", workload, "--seed", str(2 ** 31 + 77),
                       "--seconds", "0.5", "--trace", "0"],
                      devices=jax.devices()[:1])
    assert rc == 0
    out = capsys.readouterr()
    last = out.out.strip().splitlines()[-1]
    line = json.loads(last)
    assert list(line)[:5] == RESULT_KEYS and list(line)[-1] == "compared"
    assert set(line) == set(RESULT_KEYS) | {"compared"}
    assert line["correct"] is True, out.err[-2000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", metric}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]
    # each number compared beside its limit, last on standard error
    tail = out.err.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and "limit" in t for t in tail)


def test_the_command_refuses_anything_but_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        harness.require_chips(1)
    assert e.value.code not in (0, None) and "no TPU" in str(e.value.code)
    with pytest.raises(SystemExit):
        command.main(["--workload", "no.such.cell", "--seed", "1",
                      "--seconds", "1"])
    assert capsys.readouterr().out == ""


def test_traffic_repeats_from_a_seed_and_differs_between_seeds():
    mix = tiny.cell(tiny.TRAIN).traffic
    a = token_batches.batch_at(mix, 2 ** 31 + 5, 3)["tokens"]
    stream = token_batches.batches(mix, 2 ** 31 + 5)
    fourth = [next(stream) for _ in range(4)][3]["tokens"]
    assert np.array_equal(a, fourth)
    assert not np.array_equal(
        a, token_batches.batch_at(mix, 2 ** 31 + 6, 3)["tokens"])
    assert a.max() < mix["vocab_below"] and a.dtype == np.int32
    assert len({row.tobytes() for row in a}) == len(a)  # rows all differ

    mix = tiny.cell(tiny.SERVE).traffic
    one, again, other = (request_mix.requests(mix, s) for s in (9, 9, 10))
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               and x.max_new_tokens == y.max_new_tokens
               for x, y in zip(one, again))
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(one, other))
    # every seed offers the same lengths, each round: the seed draws which
    # prompt meets which output, and the order
    k = mix["sizes"]
    for rs in (one, other):
        for i in range(0, len(rs), k):
            assert sorted(len(r.prompt) for r in rs[i:i + k]) == sorted(
                request_mix.round_lengths(mix["prompt"], k))
            assert sorted(r.max_new_tokens for r in rs[i:i + k]) == sorted(
                request_mix.round_lengths(mix["output"], k))
    pairs = lambda rs: [(len(r.prompt), r.max_new_tokens) for r in rs]  # noqa: E731
    assert pairs(one) != pairs(other)
    assert sorted(pairs(one)) != sorted(pairs(other))  # pairing moves too
    assert all(r.due == 0.0 for r in one)  # a backlog
    limit = tiny.cell(tiny.SERVE).config["n_positions"]
    assert all(len(r.prompt) + r.max_new_tokens <= limit for r in one)


def test_full_size_mix_is_the_sources_lengths_inside_the_context():
    cell = tiny._load_cell(tiny.SERVE)
    mix = cell.traffic
    reqs = request_mix.requests(mix, 2 ** 31 + 1)
    assert len(reqs) == 512
    assert max(len(r.prompt) + r.max_new_tokens for r in reqs) <= 1024
    assert max(int(r.prompt.max()) for r in reqs) < 50257
    # a prompt is one prefill chunk: the mix's "clip" says so
    chunk = cell.config["deployment"]["prefill_chunk"]
    assert max(len(r.prompt) for r in reqs) <= mix["prompt"]["max"] <= chunk
    # the round's lengths keep the source's means to within what clipping
    # the log-normal's tail at its (k + 1/2) / n quantile takes away
    for key in ("prompt", "output"):
        got = request_mix.round_lengths(mix[key], mix["sizes"])
        assert 0.95 < got.mean() / mix[key]["mean"] <= 1.0
        assert len(set(got.tolist())) >= mix["sizes"] - 2  # lengths differ


def test_plain_reference_agrees_with_the_package():
    """Float32 on both sides at the tiny size: loss, gradient norms and
    logits to rounding."""
    from distributed_tensorflow_guide_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
        make_lm_loss_fn,
    )

    cell = tiny.cell(tiny.TRAIN, dtype="float32")
    z = weights.sizes_of(cell.config)
    seed = weights.seed_arg(2 ** 31 + 12345)
    cfg = TransformerConfig(
        vocab_size=z["vocab"], num_layers=z["L"], num_heads=z["h"],
        d_model=z["d"], d_ff=z["ff"], max_len=z["positions"],
        dtype=jnp.float32, attn_impl="dense")
    model = Transformer(cfg)
    theirs = jax.jit(lambda s: weights.flax_tree(s, z))(seed)
    ours = jax.jit(lambda s: weights.stacked_tree(s, z))(seed)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, z["positions"]), jnp.int32))
    import flax.linen as nn

    declared = nn.meta.unbox(shapes["params"])
    assert jax.tree.structure(declared) == jax.tree.structure(theirs)
    assert ([a.shape for a in jax.tree.leaves(declared)]
            == [a.shape for a in jax.tree.leaves(theirs)])

    tokens = token_batches.batch_at(cell.traffic, 5, 0)["tokens"]
    (loss, _), grads = jax.value_and_grad(
        make_lm_loss_fn(model, fused_ce=False), has_aux=True)(
            theirs, {"tokens": tokens})
    ref_loss, ref_grads = jax.jit(
        lambda p, b: gpt2.loss_and_grad(p, b, eps=1e-6))(ours, tokens)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    got = np.asarray(weights.norms_of_flax(grads, z))
    want = np.asarray(weights.norms_of_stacked(ref_grads))
    assert len(got) == len(weights.flat_names(z))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-9)

    logits = model.apply({"params": theirs}, tokens[:1])[0]
    hid = gpt2.hidden(ours, jnp.asarray(tokens[0]), eps=1e-6)
    np.testing.assert_allclose(np.asarray(gpt2.logits_at(ours, hid)),
                               np.asarray(logits), rtol=2e-4, atol=2e-5)


def test_adamw_written_out_is_optax_adamw():
    import optax

    opt = dict(tiny.cell(tiny.TRAIN).config["deployment"]["optimizer"])
    assert opt.pop("name") == "adamw"
    key = jax.random.PRNGKey(0)
    p = {"a": jax.random.normal(key, (5, 3)), "b": jnp.ones((4,))}
    tx = optax.adamw(**opt)
    state = tx.init(p)
    theirs, ours = p, p
    mu = nu = jax.tree.map(jnp.zeros_like, p)
    for t in range(1, 4):
        g = jax.tree.map(lambda x: jnp.sin(x * t), theirs)
        upd, state = tx.update(g, state, theirs)
        theirs = optax.apply_updates(theirs, upd)
        ours, mu, nu = gpt2.adamw_step(ours, mu, nu, g, t, opt)
    for a, b in zip(jax.tree.leaves(theirs), jax.tree.leaves(ours)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)
