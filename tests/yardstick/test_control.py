"""The comparison that decides ``correct`` has to fail what it should: the
control (the reference with int8 operands in the program's place) and each
fault a cell can have, planted under a whole run of the harness with the
look for a chip skipped. At a size the CPU holds; ``yardstick/control.py``
reads the same numbers on the chip at the cells' own sizes."""

from __future__ import annotations

import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.yardstick import tiny
from yardstick import compare, control, harness
from yardstick import run as command


def run_with(monkeypatch, capsys, workload, attr, wrapper) -> dict:
    """A whole run of the command with the driver's timed path wrapped."""
    cell = tiny.cell(workload)
    real = cell.driver.Driver

    def broken(*args, **kwargs):
        driver = real(*args, **kwargs)
        setattr(driver, attr, wrapper)
        return driver

    monkeypatch.setattr(cell.driver, "Driver", broken)
    rc = command.main(["--workload", workload, "--seed", "4242",
                       "--seconds", "0.4", "--trace", "0"],
                      devices=jax.devices()[:1])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def unchanged_state(step):
    """The step computes its metrics and hands its state back as it was."""
    def faulty(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return faulty


def half_batch(step):
    """Half of the batch is left out and the mean taken over the rest."""
    def faulty(state, batch):
        tokens = batch["tokens"]
        half = tokens.shape[0] // 2
        return step(state, {"tokens": jnp.concatenate(
            [tokens[:half], tokens[:half]])})
    return faulty


@pytest.mark.parametrize("fault,fails", [
    (unchanged_state, "change_norm"), (half_batch, "first_grad_norm")])
def test_a_broken_train_step_is_not_correct(tiny_cells, monkeypatch, capsys,
                                            fault, fails):
    line = run_with(monkeypatch, capsys, tiny.TRAIN, "wrap_step", fault)
    assert line["correct"] is False
    bad = line["compared"][fails]
    assert bad["value"] > bad["limit"]
    if fault is unchanged_state:  # no leaf moved: the gap of norms is 1
        assert bad["value"] == pytest.approx(1.0, abs=1e-6)


def altered_token(eng):
    """One decode launch in three hands back tokens that are one off."""
    fns = vars(eng.fns).copy()
    decode, calls = fns["decode"], {"n": 0}

    def faulty(*args):
        nxt, pool = decode(*args)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            nxt = (nxt + 1) % eng.fns.cfg.vocab_size
        return nxt, pool

    eng.fns = SimpleNamespace(**{**fns, "decode": faulty})
    return eng


def test_an_altered_token_is_not_correct(tiny_cells, monkeypatch, capsys):
    line = run_with(monkeypatch, capsys, tiny.SERVE, "wrap_engine",
                    altered_token)
    assert line["correct"] is False
    gap = line["compared"]["served_logit_gap_mean"]
    assert gap["value"] > 10 * gap["limit"]


def test_sound_runs_pass_the_same_limits(tiny_cells, monkeypatch, capsys):
    for workload, attr in ((tiny.TRAIN, "wrap_step"),
                           (tiny.SERVE, "wrap_engine")):
        line = run_with(monkeypatch, capsys, workload, attr, lambda x: x)
        assert line["correct"] is True, line["compared"]


#: limits a float32 program keeps a hundred times over at the tiny size
TIGHT = {"loss_step2": 4e-4, "first_grad_norm": 3e-4, "change_norm": 1e-3}


def test_the_control_comes_out_not_correct(tiny_cells, monkeypatch, capsys):
    """``control.py`` end to end, as the chip runs it: the program, the
    control and each fault read by the lines a run is read by, held to the
    cell's limits, and exit code 0 only where the program is correct and
    the control and every fault are not. Two layers of width 64 in bfloat16
    are themselves as coarse as int8 operands, so here the program runs in
    float32 and the limits lie between it and the control; on the chip the
    program runs as the configuration states and the limits are the
    cell's (readings in PERF.md)."""
    monkeypatch.setattr(harness, "load_cell", lambda name, *a, **k:
                        tiny.cell(name, dtype="float32"))
    monkeypatch.setattr(compare, "load_limits", lambda name: TIGHT)
    rc = control.main(["--workload", tiny.TRAIN, "--seeds", "11,12"],
                      devices=jax.devices()[:1])
    rows = [json.loads(x) for x in
            capsys.readouterr().out.strip().splitlines()[-2:]]
    assert rc == 0
    for row in rows:
        assert row["correct"] == {"program": True, "control": False,
                                  "half_batch": False,
                                  "unchanged_state": False}
        assert set(TIGHT) <= set(row["program"]) == set(row["control"])
        assert row["control"]["first_grad_norm"] > 10 * TIGHT[
            "first_grad_norm"]
        frozen = row["faults"]["unchanged_state"]
        assert frozen["change_norm"] == pytest.approx(1.0)
        assert frozen["loss_step1"] == 0.0
        assert frozen["first_grad_norm"] == 0.0 and frozen["loss_step2"] > 0
    # a control that passes is what the exit code is there to catch
    monkeypatch.setattr(compare, "load_limits", lambda name: {
        **TIGHT, "first_grad_norm": 0.5, "change_norm": 0.5})
    assert control.main(["--workload", tiny.TRAIN, "--seeds", "11"],
                        devices=jax.devices()[:1]) == 1
    capsys.readouterr()


def test_the_serving_control_is_read_at_the_served_positions(tiny_cells,
                                                             capsys):
    """The serving control needs no decoding: at each served position, the
    gap of the token int8 operands put first. Over some fifty tokens of a
    two-layer model it often flips none, so what is held here is that it
    is read and never reads below the program; that it comes out not
    correct is ``control.py``'s exit code on the chip."""
    control.main(["--workload", tiny.SERVE, "--seeds", "13",
                  "--seconds", "0.3"], devices=jax.devices()[:1])
    serve = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert serve["checked_tokens"] > 0
    assert set(serve["correct"]) == {"program", "control"}
    assert serve["correct"]["program"] is True
    for key, limit in tiny.LIMITS[tiny.SERVE].items():
        assert serve["program"][key] <= limit
        assert serve["control"][key] >= serve["program"][key]


def test_the_control_rounds_the_backward_pass_too():
    """Every projection product of the control has 8-bit operands, the
    two of the backward pass among them: rounding the forward pass alone
    and differentiating in float32 read closer to the reference than
    bfloat16 does (PERF.md)."""
    from yardstick.reference import gpt2

    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 4))
    g = jax.random.normal(jax.random.PRNGKey(2), (16, 4)) ** 3  # tails

    def loss(dot):
        return lambda x, w: jnp.sum(dot(x, w) * g)

    dx, dw = jax.grad(loss(lambda x, w: gpt2._dot(x, w, "int8")), (0, 1))(
        x, w)
    g8 = gpt2._int8(g)
    np.testing.assert_allclose(dx, g8 @ gpt2._int8(w).T, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(dw, gpt2._int8(x).T @ g8, rtol=1e-5,
                               atol=1e-5)
    exact = jax.grad(loss(lambda x, w: gpt2._dot(x, w, "float32")), (0, 1))(
        x, w)
    assert float(jnp.max(jnp.abs(dx - exact[0]))) > 1e-3
    levels = np.unique(np.round(np.asarray(gpt2._int8(x)) * 127
                                / float(jnp.max(jnp.abs(x))), 3))
    assert len(levels) <= 255
    with pytest.raises(ValueError):
        gpt2._dot(x, w, "fp4")


def test_worst_leaf_gap_by_hand():
    ref = np.array([1.0, 2.0, 4.0, 1e-9])
    got = np.array([1.1, 2.0, 3.0, 2e-3])
    # median 1.5: leaf 0 gap .1/1.5, leaf 2 gap 1/4, leaf 3 gap 2e-3/1.5
    assert compare.worst_leaf_gap(got, ref) == pytest.approx(0.25)
    assert compare.worst_leaf_gap(
        got, ref, keep=np.array([True, True, False, True])) == (
            pytest.approx(0.1 / 1.5))
    # a leaf that has not moved reads 1
    assert compare.worst_leaf_gap(np.zeros(4), ref) == pytest.approx(1.0)


def test_dead_gradients_are_left_out_by_rule_not_by_name():
    ref = {"losses": [1.0], "grad_norms": np.array([1.0, 1.0, 1e-6]),
           "change_norms": np.array([1.0, 1.0, 1.0])}
    got = {"losses": [1.0], "grad_norms": np.array([1.0, 1.0, 1e-6]),
           "change_norms": np.array([1.0, 1.0, 3.0])}  # round-off's leaf
    assert compare.train_numbers(got, ref)["change_norm"] == 0.0
    with pytest.raises(KeyError):
        compare.against_limits({"a": 1.0}, {"b": 1.0})
    assert not harness.verdict([])  # nothing compared is not correct
    assert not harness.verdict([harness.Compared("x", float("nan"), 1.0)])
