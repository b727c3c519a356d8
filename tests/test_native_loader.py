"""Native C++ data loader vs its pure-Python twin: byte-identical streams,
shard disjointness, epoch reshuffling, structured field decoding."""

import numpy as np
import pytest

from distributed_tensorflow_guide_tpu.data.native_loader import (
    ImageAugment,
    NativeRecordLoader,
    PyRecordLoader,
    epoch_permutation,
    load_native_lib,
    make_fields,
    open_record_loader,
    write_records,
)

FIELDS = make_fields({
    "image": (np.float32, (4, 4, 1)),
    "label": (np.int32, ()),
})


@pytest.fixture(scope="module")
def record_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("data") / "train.records"
    rng = np.random.RandomState(0)
    n = 256
    cols = {
        "image": rng.randn(n, 4, 4, 1).astype(np.float32),
        "label": np.arange(n, dtype=np.int32),
    }
    write_records(path, cols, FIELDS)
    return path, cols


needs_native = pytest.mark.skipif(load_native_lib() is None,
                                  reason="no g++ toolchain")


def test_append_validates_record_size(tmp_path):
    """The format is headerless fixed-size records; appending with a
    different field layout must refuse instead of silently corrupting the
    stream (round-4 advisor)."""
    path = tmp_path / "x.records"
    cols = {"image": np.zeros((4, 4, 4, 1), np.float32),
            "label": np.arange(4, dtype=np.int32)}
    write_records(path, cols, FIELDS)
    # same layout appends fine (and append-to-missing == fresh write)
    write_records(path, cols, FIELDS, append=True)
    # 20-byte records over a 68-byte-record file: size check fires. (A
    # layout whose record size happens to DIVIDE the existing bytes is
    # undetectable in a headerless format — the check is best-effort.)
    other = make_fields({"vec": (np.float32, (5,))})
    with pytest.raises(ValueError, match="record_bytes"):
        write_records(path, {"vec": np.zeros((4, 5), np.float32)}, other,
                      append=True)
    fresh = tmp_path / "y.records"
    write_records(fresh, {"vec": np.zeros((4, 5), np.float32)}, other,
                  append=True)
    assert fresh.stat().st_size == 4 * 20


def test_permutation_is_deterministic_and_complete():
    p1 = epoch_permutation(100, seed=7, epoch=3)
    p2 = epoch_permutation(100, seed=7, epoch=3)
    assert np.array_equal(p1, p2)
    assert sorted(p1) == list(range(100))
    assert not np.array_equal(p1, epoch_permutation(100, seed=7, epoch=4))
    assert not np.array_equal(p1, epoch_permutation(100, seed=8, epoch=3))


def test_python_loader_decodes_fields(record_file):
    path, cols = record_file
    dl = PyRecordLoader(path, FIELDS, batch_size=32, shuffle=False)
    b = dl.next_batch()
    assert b["image"].shape == (32, 4, 4, 1)
    assert b["label"].shape == (32,)
    np.testing.assert_array_equal(b["label"], np.arange(32))
    np.testing.assert_array_equal(b["image"], cols["image"][:32])


@needs_native
def test_native_matches_python_twin(record_file):
    path, _ = record_file
    kw = dict(batch_size=16, shuffle=True, seed=11)
    native = NativeRecordLoader(path, FIELDS, **kw)
    twin = PyRecordLoader(path, FIELDS, **kw)
    assert native.batches_per_epoch == twin.batches_per_epoch == 16
    # two full epochs: crossing the boundary must reshuffle identically
    for _ in range(2 * native.batches_per_epoch):
        nb, pb = native.next_batch(), twin.next_batch()
        np.testing.assert_array_equal(nb["label"], pb["label"])
        np.testing.assert_array_equal(nb["image"], pb["image"])
    native.close()


@needs_native
def test_native_shards_are_disjoint_and_cover(record_file):
    path, _ = record_file
    seen = []
    for shard in range(4):
        dl = NativeRecordLoader(path, FIELDS, batch_size=16, shard_id=shard,
                                num_shards=4, shuffle=True, seed=5)
        labels = np.concatenate([dl.next_batch()["label"]
                                 for _ in range(dl.batches_per_epoch)])
        seen.append(labels)
        dl.close()
    allseen = np.concatenate(seen)
    assert len(allseen) == 256
    assert len(set(allseen.tolist())) == 256  # disjoint cover, no dupes


@needs_native
def test_native_epoch_order_differs(record_file):
    path, _ = record_file
    dl = NativeRecordLoader(path, FIELDS, batch_size=64, shuffle=True, seed=1)
    e0 = np.concatenate([dl.next_batch()["label"] for _ in range(4)])
    e1 = np.concatenate([dl.next_batch()["label"] for _ in range(4)])
    dl.close()
    assert sorted(e0.tolist()) == sorted(e1.tolist()) == list(range(256))
    assert not np.array_equal(e0, e1)


@needs_native
def test_native_rejects_bad_files(tmp_path):
    bad = tmp_path / "bad.records"
    bad.write_bytes(b"\x00" * 37)  # not a whole number of records
    with pytest.raises(ValueError):
        NativeRecordLoader(bad, FIELDS, batch_size=4)


def test_open_record_loader_falls_back(record_file, monkeypatch):
    path, _ = record_file
    import distributed_tensorflow_guide_tpu.data.native_loader as nl

    monkeypatch.setattr(nl, "load_native_lib", lambda: None)
    dl = open_record_loader(path, FIELDS, 16, shuffle=False, prefetch=2)
    assert isinstance(dl, PyRecordLoader)
    assert dl.next_batch()["label"].shape == (16,)


def test_native_lib_is_keyed_by_what_the_source_says(tmp_path, monkeypatch):
    """A copied checkout has new mtimes and the same code: same library.
    An edit changes the key whatever the clock says."""
    import hashlib
    import os

    import distributed_tensorflow_guide_tpu.data.native_loader as nl

    src = tmp_path / "dataloader.cpp"
    src.write_bytes(nl._SRC.read_bytes())
    os.utime(src, (1, 1))
    monkeypatch.setattr(nl, "_SRC", src)
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    built = tmp_path / f"dataloader_{digest}.so"
    built.write_bytes(b"stands for the library built from this source")
    assert nl._build_lib(tmp_path) == built  # found, not rebuilt
    os.utime(src, (2, 2))
    assert nl._build_lib(tmp_path) == built
    assert built.read_bytes().startswith(b"stands for")


@needs_native
def test_a_loader_that_cannot_be_built_is_an_error(tmp_path, monkeypatch):
    """With a toolchain, a build failure means the source is broken; the
    Python twin must not paper over it. Without one, the twin is the
    designed path."""
    import distributed_tensorflow_guide_tpu.data.native_loader as nl

    src = tmp_path / "dataloader.cpp"
    src.write_text("this is not C++;\n")
    monkeypatch.setattr(nl, "_SRC", src)
    monkeypatch.setenv("DTG_NATIVE_CACHE", str(tmp_path / "cache"))
    with pytest.raises(RuntimeError, match="failed to build"):
        load_native_lib()
    monkeypatch.setattr(nl.shutil, "which", lambda name: None)
    assert load_native_lib() is None


@needs_native
def test_native_pooled_gather_large_records(tmp_path):
    # batch*record > 64KB exercises the persistent worker pool (small
    # batches are copied inline by the producer)
    fields = make_fields({"x": (np.float32, (1024,))})  # 4KB records
    rng = np.random.RandomState(1)
    cols = {"x": rng.randn(128, 1024).astype(np.float32)}
    path = tmp_path / "big.records"
    write_records(path, cols, fields)
    kw = dict(batch_size=32, shuffle=True, seed=9)
    native = NativeRecordLoader(path, fields, n_threads=4, **kw)
    twin = PyRecordLoader(path, fields, **kw)
    for _ in range(3 * native.batches_per_epoch):
        np.testing.assert_array_equal(native.next_batch()["x"],
                                      twin.next_batch()["x"])
    native.close()


@needs_native
def test_native_prefetch_throughput_smoke(record_file):
    # not a benchmark — just proves the ring survives rapid consumption
    path, _ = record_file
    dl = NativeRecordLoader(path, FIELDS, batch_size=8, prefetch=8,
                            n_threads=2, shuffle=True, seed=3)
    for _ in range(200):  # ~6 epochs through the rollover path
        dl.next_batch()
    dl.close()


@needs_native
def test_native_loader_feeds_pipelined_lm(tmp_path):
    """Composition: the C++ record stream feeds the GPT-2 pipeline strategy
    (token records -> microbatch reshape -> dp x pp mesh), not just MNIST
    DP — the reference's data path works with every strategy family."""
    import jax
    import jax.numpy as jnp
    import optax

    from distributed_tensorflow_guide_tpu.core.mesh import (
        MeshSpec,
        build_mesh,
    )
    from distributed_tensorflow_guide_tpu.models.transformer import (
        TransformerConfig,
    )
    from distributed_tensorflow_guide_tpu.parallel.pipeline import PipelinedLM

    cfg = TransformerConfig(
        vocab_size=64, num_layers=4, num_heads=2, d_model=32, d_ff=64,
        max_len=16, causal=True, dtype=jnp.float32,
    )
    M, mb = 2, 2  # microbatches x microbatch rows per data shard
    mesh = build_mesh(MeshSpec(data=2, pipe=4))
    lm = PipelinedLM(mesh, cfg, num_microbatches=M)
    params = lm.init_params(jax.random.PRNGKey(0))
    tx = optax.adam(1e-3)
    opt_state = lm.init_opt_state(tx, params)
    step = lm.make_train_step(tx, params, donate=False)

    # token records on disk -> native stream -> global batch (B, S)
    rng = np.random.RandomState(0)
    n_records = 64
    fields = make_fields({"tokens": (np.int32, (cfg.max_len,))})
    path = tmp_path / "tokens.rec"
    write_records(path, {
        "tokens": rng.randint(0, cfg.vocab_size,
                              (n_records, cfg.max_len)).astype(np.int32)
    }, fields)

    B = M * mb * mesh.shape["data"]
    loader = NativeRecordLoader(path, fields, batch_size=B, seed=3)
    losses = []
    for _ in range(3):
        batch = loader.next_batch()
        _opt, params_new, mets = step(opt_state, params,
                                      jnp.asarray(batch["tokens"]))
        opt_state, params = _opt, params_new
        losses.append(float(mets["loss"]))
    assert all(np.isfinite(losses)), losses
    assert loader.num_records == n_records
    loader.close()


# -- train-time image augmentation (round-5: crop+flip in the loader tier) ---

AUG_FIELDS = make_fields({
    "image": (np.uint8, (40, 40, 3)),
    "label": (np.int32, ()),
})
AUG = ImageAugment(in_shape=(40, 40, 3), crop=(32, 32), hflip=True)


@pytest.fixture(scope="module")
def aug_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("aug") / "imgs.records"
    rng = np.random.RandomState(3)
    n = 64
    cols = {"image": rng.randint(0, 256, (n, 40, 40, 3)).astype(np.uint8),
            "label": np.arange(n, dtype=np.int32)}
    write_records(path, cols, AUG_FIELDS)
    return path, cols


@needs_native
def test_augmented_native_matches_python_twin(aug_file):
    """The bit-identical-streams contract extends to augmentation: the C++
    gather-copy crop/flip and the Python twin agree byte-for-byte, across
    an epoch boundary (epoch is part of the draw seed)."""
    path, _ = aug_file
    kw = dict(batch_size=8, shuffle=True, seed=5, augment=AUG)
    nat = NativeRecordLoader(path, AUG_FIELDS, **kw)
    py = PyRecordLoader(path, AUG_FIELDS, **kw)
    assert nat.batches_per_epoch == py.batches_per_epoch == 8
    for i in range(20):  # 2.5 epochs
        a, b = nat.next_batch(), py.next_batch()
        assert a["image"].shape == (8, 32, 32, 3)
        np.testing.assert_array_equal(a["image"], b["image"], err_msg=str(i))
        np.testing.assert_array_equal(a["label"], b["label"])
    nat.close()


def test_augmentation_pinned_to_seed_epoch_index(aug_file):
    """The determinism contract: draws are a pure function of
    (seed, epoch, record index) — invariant to shuffle order; changed by
    epoch and by seed."""
    path, cols = aug_file
    # unshuffled epoch 0: record r of batch 0 is global index r
    py = PyRecordLoader(path, AUG_FIELDS, batch_size=64, shuffle=False,
                        seed=5, augment=AUG)
    plain = py.next_batch()

    # same records reached through a SHUFFLED loader get the SAME crops:
    # find each record by label and compare
    sh = PyRecordLoader(path, AUG_FIELDS, batch_size=64, shuffle=True,
                        seed=5, augment=AUG)
    shuffled = sh.next_batch()
    order = np.argsort(shuffled["label"])
    np.testing.assert_array_equal(shuffled["image"][order], plain["image"])

    # epoch 1 re-crops (epoch is in the seed): some record must differ
    e1 = py.next_batch()  # advances to epoch 1 (64 = one full epoch)
    assert py._epoch == 1
    assert not np.array_equal(e1["image"], plain["image"])

    # a different seed re-crops too
    other = PyRecordLoader(path, AUG_FIELDS, batch_size=64, shuffle=False,
                           seed=6, augment=AUG)
    assert not np.array_equal(other.next_batch()["image"], plain["image"])

    # crops are genuine views of the stored image: every augmented image
    # appears somewhere in its source (check one record exhaustively)
    src = cols["image"][0]
    out = plain["image"][0]
    found = any(
        np.array_equal(src[y:y + 32, x:x + 32], cand)
        for cand in (out, out[:, ::-1])
        for y in range(9) for x in range(9)
    )
    assert found


def test_augment_spec_validation(aug_file):
    path, _ = aug_file
    with pytest.raises(ValueError, match="must fit"):
        ImageAugment(in_shape=(40, 40, 3), crop=(41, 32))
    # leading field must be the uint8 image at the declared shape
    bad = make_fields({"label": (np.int32, ()),
                       "image": (np.uint8, (40, 40, 3))})
    with pytest.raises(ValueError, match="leading uint8 image"):
        PyRecordLoader(path, bad, batch_size=8, augment=AUG)
