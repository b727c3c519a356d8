"""Multi-process harness: real multi-controller JAX on one machine.

The targets below run in fresh subprocesses (separate GIL, separate JAX
runtime, Gloo collectives between them) — the TPU-native analogue of TF's
MultiProcessRunner tests (SURVEY.md §4 test plan, row 5).
"""

import time

import pytest

from distributed_tensorflow_guide_tpu.runtime.multiprocess import (
    MultiProcessError,
    MultiProcessRunner,
    run_multiprocess,
)

N = 2  # processes; 2 local devices each → 4-device global mesh


# ---- targets (must be module-level: imported by path in the subprocess) ----


def _target_global_psum(scale):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=-1))
    pid = jax.process_index()
    local = np.full((2 * jax.local_device_count(),), float(pid + 1) * scale,
                    np.float32)
    x = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(("data", "model", "pipe", "context"))), local
    )
    total = jax.jit(
        jnp.sum, out_shardings=NamedSharding(mesh, P())
    )(x)
    return {
        "pid": pid,
        "nproc": jax.process_count(),
        "global_devices": jax.device_count(),
        "sum": float(total),
    }


def _target_dp_local_shards(steps):
    """Sync-DP trains from per-process local batches (the multi-host input
    contract of DataParallel.shard_batch) and must match the single-process
    trajectory on the same global batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax.training import train_state

    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.parallel.data_parallel import (
        DataParallel,
    )

    mesh = build_mesh(MeshSpec(data=-1))
    dp = DataParallel(mesh)

    # Deterministic global batch; every process slices out its own share.
    rng = np.random.RandomState(0)
    gx = rng.randn(8, 4).astype(np.float32)
    gw = np.arange(4, dtype=np.float32)
    gy = gx @ gw
    per = 8 // jax.process_count()
    lo = jax.process_index() * per
    local = {"x": gx[lo:lo + per], "y": gy[lo:lo + per]}

    def apply_fn(variables, x):
        return x @ variables["params"]["w"]

    state = dp.replicate(train_state.TrainState.create(
        apply_fn=apply_fn,
        params={"w": jnp.zeros(4, jnp.float32)},
        tx=optax.sgd(0.1),
    ))

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {}

    step = dp.make_train_step(loss_fn, donate=False)
    losses = []
    for _ in range(steps):
        state, mets = step(state, dp.shard_batch(local))
        losses.append(float(mets["loss"]))
    return {"pid": jax.process_index(), "losses": losses,
            "w": np.asarray(state.params["w"]).tolist()}


def _target_fsdp_sharded_step(steps):
    """GSPMD param-sharded (ZeRO-3) TRAINING spanning processes: params and
    moments live in NamedSharding shards across both processes' devices —
    the multi-controller capability shard_map collectives alone don't prove."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax.training import train_state
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.parallel.fsdp import FSDP

    mesh = build_mesh(MeshSpec(data=-1))
    fsdp = FSDP(mesh, min_shard_size=4)

    def init_fn():
        return {"w": jnp.zeros((8, 4), jnp.float32)}

    params, shardings = fsdp.init_params(init_fn)
    state = train_state.TrainState.create(
        apply_fn=None, params=params, tx=optax.sgd(0.1)
    )
    st_sh = fsdp.state_shardings(state, shardings)
    state = jax.device_put(state, st_sh)

    rng = np.random.RandomState(1)
    gx = rng.randn(8, 8).astype(np.float32)
    gy = rng.randn(8, 4).astype(np.float32)
    per = 8 // jax.process_count()
    lo = jax.process_index() * per

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    step = fsdp.make_train_step(loss_fn, st_sh, donate=False)
    batch = {
        k: jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("data")), v[lo:lo + per]
        )
        for k, v in (("x", gx), ("y", gy))
    }
    losses = []
    for _ in range(steps):
        state, mets = step(state, batch)
        losses.append(float(mets["loss"]))
    w_spec = tuple(state.params["w"].sharding.spec)
    return {"pid": jax.process_index(), "losses": losses,
            "w_spec": [str(x) for x in w_spec]}


def _target_pipeline_across_processes(steps):
    """dp x pp pipeline TRAINING spanning processes: the pipe axis's
    per-tick ppermute hand-offs cross the process boundary over Gloo —
    the multi-controller capability the in-process pipeline tests don't
    prove. Params are materialized into their global shard layout with
    make_array_from_callback over the host-replicated init (device_put
    cannot target another process's shards)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.models.transformer import (
        TransformerConfig,
    )
    from distributed_tensorflow_guide_tpu.parallel.pipeline import PipelinedLM

    cfg = TransformerConfig(
        vocab_size=32, num_layers=2, num_heads=2, d_model=16, d_ff=32,
        max_len=8, causal=True, dtype=jnp.float32,
    )
    mesh = build_mesh(MeshSpec(data=2, pipe=2))
    pp = PipelinedLM(mesh, cfg, num_microbatches=2)
    params = pp.init_params_multihost(jax.random.PRNGKey(0))
    tx = optax.sgd(0.1)
    opt_state = pp.init_opt_state(tx, params)
    step = pp.make_train_step(tx, params, donate=False)

    rng = np.random.RandomState(0)
    tokens_global = rng.randint(0, cfg.vocab_size, (8, cfg.max_len)).astype(
        np.int32
    )
    per = 8 // jax.process_count()
    lo = jax.process_index() * per
    tokens = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("data")), tokens_global[lo:lo + per]
    )
    losses = []
    for _ in range(steps):
        opt_state, params, m = step(opt_state, params, tokens)
        losses.append(float(m["loss"]))
    return {"pid": jax.process_index(), "losses": losses}


def _target_preemptible_training(ckpt_dir, max_steps):
    """TrainLoop + PreemptionHook under multi-controller: the parent
    SIGTERMs ONLY process 0; the hook's cross-process agreement must make
    BOTH processes save at the same step and stop cleanly."""
    import pathlib
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.train.checkpoint import Checkpointer
    from distributed_tensorflow_guide_tpu.train.elastic import PreemptionHook
    from distributed_tensorflow_guide_tpu.train.hooks import StopAtStepHook
    from distributed_tensorflow_guide_tpu.train.loop import TrainLoop

    # GLOBAL replicated state: orbax's multi-host save refuses host-local
    # arrays, and a real multi-controller train state is global anyway
    mesh = build_mesh(MeshSpec(data=-1))
    w0 = jax.make_array_from_callback(
        (), NamedSharding(mesh, P()), lambda idx: np.zeros((), np.float32)
    )

    def step_fn(state, batch):
        _time.sleep(0.15)  # a real step's width: the signal lands mid-run
        return {"w": state["w"] + 1.0}, {"loss": jnp.float32(0.0)}

    ckpt = Checkpointer(ckpt_dir)
    hook = PreemptionHook(ckpt)
    loop = TrainLoop(step_fn, {"w": w0}, iter(lambda: 0, 1),
                     hooks=[StopAtStepHook(max_steps), hook])
    # readiness marker AFTER the handler is installed (begin runs in
    # loop.run) — so run one warmup step via the loop's own machinery:
    # write the marker from a hook-free vantage instead
    marker = pathlib.Path(ckpt_dir) / f"ready_{jax.process_index()}"

    class _Ready:
        def begin(self, loop):
            pass

        def after_step(self, step, metrics):
            if step == 0:
                marker.touch()

        def end(self, step):
            pass

    loop.hooks = list(loop.hooks) + [_Ready()]
    final = loop.run()
    ckpt.close()
    return {
        "pid": jax.process_index(),
        "preempted_at": hook.preempted_at,
        "steps_run": loop.step,
        "w": float(final["w"]),
    }


def _target_one_proc_fails():
    import jax

    if jax.process_index() == 1:
        raise RuntimeError("injected failure on process 1")
    return {"pid": jax.process_index()}


def _target_sleep_forever():
    import jax  # noqa: F401  (init done by bootstrap)

    time.sleep(600)
    return {}


# ---- tests -----------------------------------------------------------------


def test_cross_process_collectives():
    results = run_multiprocess(
        _target_global_psum, N, args=(2.0,), local_devices_per_process=2
    )
    assert [r.ok for r in results] == [True] * N
    for r in results:
        assert r.result["nproc"] == N
        assert r.result["global_devices"] == 2 * N
        # sum over 4 elems of 1*2.0 from pid0 + 4 elems of 2*2.0 from pid1
        assert r.result["sum"] == pytest.approx(24.0)


def test_dp_from_process_local_batches_matches_single_process():
    import numpy as np

    steps = 5
    results = run_multiprocess(
        _target_dp_local_shards, N, args=(steps,),
        local_devices_per_process=2,
    )
    # Single-process reference: full-batch GD on the identical problem
    # (pmean of shard grads == global-batch grad).
    rng = np.random.RandomState(0)
    gx = rng.randn(8, 4).astype(np.float32)
    gw = np.arange(4, dtype=np.float32)
    gy = gx @ gw
    w = np.zeros(4, np.float32)
    ref_losses = []
    for _ in range(steps):
        pred = gx @ w
        ref_losses.append(float(np.mean((pred - gy) ** 2)))
        w = w - 0.1 * (2.0 / len(gx)) * gx.T @ (pred - gy)
    for r in results:
        assert r.result["losses"] == pytest.approx(ref_losses, rel=1e-4)
        assert r.result["w"] == pytest.approx(w.tolist(), rel=1e-4)


def test_fsdp_sharded_training_across_processes():
    """ZeRO-3 across processes matches the single-process trajectory and
    the params really live sharded over the cross-process data axis."""
    import numpy as np

    steps = 4
    results = run_multiprocess(
        _target_fsdp_sharded_step, N, args=(steps,),
        local_devices_per_process=2,
    )
    assert [r.ok for r in results] == [True] * N
    for r in results:
        assert "data" in r.result["w_spec"], r.result

    # single-(this-)process reference on the same problem, plain GD
    rng = np.random.RandomState(1)
    gx = rng.randn(8, 8).astype(np.float32)
    gy = rng.randn(8, 4).astype(np.float32)
    w = np.zeros((8, 4), np.float32)
    ref = []
    for _ in range(steps):
        pred = gx @ w
        ref.append(float(np.mean((pred - gy) ** 2)))
        grad = 2.0 * gx.T @ (pred - gy) / pred.size
        w -= 0.1 * grad
    for r in results:
        np.testing.assert_allclose(r.result["losses"], ref, rtol=1e-4)


def test_pipeline_training_across_processes():
    """dp x pp across 2 processes (Gloo ppermute between them) matches the
    in-process run of the identical config bit-for-bit at f32 tolerance —
    the pipeline's multi-host story, not just its fake-mesh one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
    from distributed_tensorflow_guide_tpu.models.transformer import (
        TransformerConfig,
    )
    from distributed_tensorflow_guide_tpu.parallel.pipeline import PipelinedLM

    steps = 3
    results = run_multiprocess(
        _target_pipeline_across_processes, N, args=(steps,),
        local_devices_per_process=2,
    )
    assert [r.ok for r in results] == [True] * N

    # in-process oracle: identical config, seed and tokens on 4 local devices
    cfg = TransformerConfig(
        vocab_size=32, num_layers=2, num_heads=2, d_model=16, d_ff=32,
        max_len=8, causal=True, dtype=jnp.float32,
    )
    mesh = build_mesh(MeshSpec(data=2, pipe=2), devices=jax.devices()[:4])
    pp = PipelinedLM(mesh, cfg, num_microbatches=2)
    params = pp.init_params(jax.random.PRNGKey(0))
    tx = optax.sgd(0.1)
    opt_state = pp.init_opt_state(tx, params)
    step = pp.make_train_step(tx, params, donate=False)
    tokens = jax.device_put(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (8, cfg.max_len))
        .astype(np.int32),
        NamedSharding(mesh, P("data")),
    )
    ref = []
    for _ in range(steps):
        opt_state, params, m = step(opt_state, params, tokens)
        ref.append(float(m["loss"]))
    for r in results:
        np.testing.assert_allclose(r.result["losses"], ref, rtol=1e-5)


def test_preemption_agreement_across_processes(tmp_path):
    """Single-host SIGTERM (process 0 only) preempts the WHOLE job
    consistently: the flag is agreed cross-process, both processes save
    the same checkpoint label and stop at the same step — no straggler,
    no hung collective save."""
    import signal

    d = str(tmp_path / "preempt")
    runner = MultiProcessRunner(
        _target_preemptible_training, N, args=(d, 400),
        local_devices_per_process=2, timeout=120,
    ).start()
    import pathlib

    deadline = time.time() + 60
    ready = [pathlib.Path(d) / f"ready_{i}" for i in range(N)]
    while time.time() < deadline and not all(m.exists() for m in ready):
        time.sleep(0.2)
    assert all(m.exists() for m in ready), "processes never reached step 1"
    runner.kill(0, signal.SIGTERM)  # ONLY process 0 gets the notice
    results = runner.join()
    assert [r.ok for r in results] == [True] * N
    labels = [r.result["preempted_at"] for r in results]
    steps = [r.result["steps_run"] for r in results]
    assert labels[0] is not None and labels[0] == labels[1], (labels, steps)
    assert steps[0] == steps[1] == labels[0], (labels, steps)
    assert steps[0] < 400  # actually preempted, not run to completion


def test_subprocess_failure_propagates():
    with pytest.raises(MultiProcessError) as exc:
        run_multiprocess(_target_one_proc_fails, N, timeout=120)
    bad = [r for r in exc.value.results if not r.ok]
    assert [r.process_id for r in bad] == [1]
    assert "injected failure on process 1" in bad[0].stderr


def test_failure_grace_reaps_peers_within_grace_window():
    """Round-10 satellite pin for the supervision core: one member exits
    nonzero → on_first_failure fires once with (pid, code), survivors get
    ``failure_grace`` seconds and are then killed — the whole join is
    bounded by the grace window, NOT the wall-clock timeout. Raw Popen
    sleepers keep this fast (no JAX boot): the semantics under test live
    entirely in supervise()."""
    import subprocess
    import sys

    from distributed_tensorflow_guide_tpu.runtime.multiprocess import (
        supervise,
    )

    procs = [
        subprocess.Popen([sys.executable, "-c", "import sys; sys.exit(3)"]),
        subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(600)"]),
        subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(600)"]),
    ]
    failures = []
    t0 = time.monotonic()
    timed_out = supervise(
        procs, timeout=300.0, failure_grace=1.0,
        on_first_failure=lambda pid, code: failures.append((pid, code)),
    )
    elapsed = time.monotonic() - t0
    assert not timed_out
    assert failures == [(0, 3)]  # fired once, with the right pid and code
    assert elapsed < 30.0  # grace + poll slack, nowhere near timeout=300
    codes = [p.returncode for p in procs]
    assert codes[0] == 3  # the failure's own exit code is preserved
    assert codes[1] is not None and codes[1] < 0  # survivors were killed
    assert codes[2] is not None and codes[2] < 0  # (negative = by signal)


@pytest.mark.chaos
def test_runner_kill_reaps_peers_within_grace_not_timeout():
    """The same pin one level up: a worker SIGKILLed mid-run makes join()
    return within the grace window against a deliberately huge timeout,
    with per-ProcessResult exit codes recorded."""
    import signal as _sig

    runner = MultiProcessRunner(
        _target_sleep_forever, N, timeout=300
    ).start()
    time.sleep(3)  # let processes boot
    t0 = time.monotonic()
    runner.kill(1)
    results = runner.join(raise_on_error=False, failure_grace=2.0)
    elapsed = time.monotonic() - t0
    assert elapsed < 60.0, "join waited toward timeout, not failure_grace"
    assert results[1].returncode == -_sig.SIGKILL  # the injected kill
    assert results[0].returncode is not None  # peer reaped, code recorded
    assert not results[1].ok


def test_fault_injection_kill_is_detected():
    runner = MultiProcessRunner(
        _target_sleep_forever, N, timeout=15
    ).start()
    time.sleep(3)  # let processes boot
    runner.kill(1)
    results = runner.join(raise_on_error=False)
    assert not results[1].ok  # SIGKILL detected, not hung (vs run.sh)
    # survivor was reaped by the supervisor rather than left dangling
    assert results[0].returncode is not None


def test_nested_target_rejected():
    def nested():  # pragma: no cover
        pass

    with pytest.raises(ValueError, match="module-level"):
        MultiProcessRunner(nested, 2)
