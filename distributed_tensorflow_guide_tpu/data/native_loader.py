"""Python surface of the native (C++) data loader.

The reference's input path is TF's compiled runtime (the wheel's native
kernels feed ``sess.run``); the guide's Python never touches a record. This
module gives the framework the same split: ``native/dataloader.cpp`` does
mmap + per-epoch global shuffle + multi-threaded batch gather + background
prefetch behind a C ABI, and this file compiles it on demand (g++ — no
pybind11 in the image; ctypes is the binding) and wraps it in an iterator of
numpy batches.

``PyRecordLoader`` is the bit-identical pure-Python twin: same xoshiro256**
RNG, same Fisher–Yates, same contiguous shard blocks — used as fallback when
no compiler is available and as the oracle in tests (native and Python
streams must match byte-for-byte).

Records are fixed-size; structured samples are described by a ``fields``
spec (name → dtype/shape) packed back-to-back, a deliberately boring format
that mmaps well — the TPU-era answer to "what replaces the feed_dict".
"""

from __future__ import annotations

import ctypes
import dataclasses
import hashlib
import logging
import os
import shutil
import subprocess
from pathlib import Path
from typing import Iterator, Mapping, Sequence

import numpy as np

log = logging.getLogger("dtg.data")

_SRC = Path(__file__).parent / "native" / "dataloader.cpp"
_LIB_CACHE: dict[str, ctypes.CDLL] = {}

MASK64 = (1 << 64) - 1


class NativeLoaderUnavailable(RuntimeError):
    """This machine has no C++ toolchain to build the loader with."""


# -- build + bind ------------------------------------------------------------


def _build_lib(cache_dir: str | Path | None = None) -> Path:
    cache_dir = Path(cache_dir or os.environ.get(
        "DTG_NATIVE_CACHE", Path.home() / ".cache" / "dtg_native"))
    cache_dir.mkdir(parents=True, exist_ok=True)
    # keyed by what the source SAYS: a copied checkout has new mtimes and
    # the same code, an edited file can keep its second
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    so = cache_dir / f"dataloader_{digest}.so"
    if so.exists():
        return so
    tmp = so.with_suffix(f".build{os.getpid()}.so")
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           str(_SRC), "-o", str(tmp)]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, so)  # atomic: concurrent builders race harmlessly
    log.info("built native dataloader: %s", so)
    return so


def load_native_lib() -> ctypes.CDLL | None:
    """Compile (cached) and bind the C ABI; None if there is no toolchain.
    With a toolchain, a build that fails raises: the source is broken, and
    the Python twin would hide it."""
    if shutil.which("g++") is None:
        log.warning("no g++: native dataloader unavailable; using Python twin")
        return None
    try:
        so = _build_lib()
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            "native dataloader failed to build:\n"
            + e.stderr.decode(errors="replace")) from e
    key = str(so)
    if key not in _LIB_CACHE:
        lib = ctypes.CDLL(key)
        lib.dl_open.restype = ctypes.c_void_p
        lib.dl_open.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_uint64,
            ctypes.c_int,
        ]
        lib.dl_open_aug.restype = ctypes.c_void_p
        lib.dl_open_aug.argtypes = lib.dl_open.argtypes + [
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,  # in_h/in_w/chan
            ctypes.c_int64, ctypes.c_int64,                  # crop_h/crop_w
            ctypes.c_int64, ctypes.c_int,                    # extra, hflip
        ]
        lib.dl_next.restype = ctypes.c_int64
        lib.dl_next.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.dl_batches_per_epoch.restype = ctypes.c_int64
        lib.dl_batches_per_epoch.argtypes = [ctypes.c_void_p]
        lib.dl_num_records.restype = ctypes.c_int64
        lib.dl_num_records.argtypes = [ctypes.c_void_p]
        lib.dl_record_bytes_out.restype = ctypes.c_int64
        lib.dl_record_bytes_out.argtypes = [ctypes.c_void_p]
        lib.dl_close.argtypes = [ctypes.c_void_p]
        _LIB_CACHE[key] = lib
    return _LIB_CACHE[key]


# -- the shared RNG/shuffle spec (python twin of the C++) --------------------


class _Xoshiro256ss:
    """Exact Python port of the C++ Rng (xoshiro256** + splitmix64 seeding +
    Lemire bounded draw). Keep in lockstep with native/dataloader.cpp."""

    def __init__(self, seed: int):
        self.s = []
        seed &= MASK64
        for _ in range(4):
            seed = (seed + 0x9E3779B97F4A7C15) & MASK64
            z = seed
            z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & MASK64
            z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & MASK64
            self.s.append(z ^ (z >> 31))

    @staticmethod
    def _rotl(x: int, k: int) -> int:
        return ((x << k) | (x >> (64 - k))) & MASK64

    def next(self) -> int:
        s = self.s
        result = (self._rotl((s[1] * 5) & MASK64, 7) * 9) & MASK64
        t = (s[1] << 17) & MASK64
        s[2] ^= s[0]
        s[3] ^= s[1]
        s[1] ^= s[2]
        s[0] ^= s[3]
        s[2] ^= t
        s[3] = self._rotl(s[3], 45)
        return result

    def bounded(self, n: int) -> int:
        x = self.next()
        m = x * n
        low = m & MASK64
        if low < n:
            t = (1 << 64) % n
            while low < t:
                x = self.next()
                m = x * n
                low = m & MASK64
        return m >> 64


def epoch_permutation(n_records: int, seed: int, epoch: int) -> np.ndarray:
    """The global shuffle both implementations use: seeded Fisher–Yates."""
    rng = _Xoshiro256ss((seed * 0x9E3779B97F4A7C15 + epoch + 1) & MASK64)
    idx = np.arange(n_records, dtype=np.int64)
    for i in range(n_records - 1, 0, -1):
        j = rng.bounded(i + 1)
        idx[i], idx[j] = idx[j], idx[i]
    return idx


# -- image augmentation (shared spec: C++ does it in the gather copy) --------


def _aug_seed(seed: int, epoch: int, idx: int) -> int:
    """Per-record augmentation seed — keep in lockstep with aug_seed() in
    native/dataloader.cpp. Pure in (seed, epoch, record index): the same
    record gets the same crop/flip in a given epoch no matter the shuffle
    order, shard layout, or loader implementation."""
    return (seed * 0x9E3779B97F4A7C15
            + (epoch + 1) * 0xBF58476D1CE4E5B9 + idx) & MASK64


@dataclasses.dataclass(frozen=True)
class ImageAugment:
    """Deterministic train-time crop+flip, applied by the loader tier.

    Records store a slightly-larger-than-train image, e.g. (256, 256, 3)
    uint8, cropped to (224, 224) per epoch — the classic ImageNet recipe's
    geometry without JPEG (this environment has no image corpus; decoded-
    pixel records at the right byte scale are the honest contract). The
    C++ loader augments DURING the gather copy (one pass over the bytes,
    same cost class as the memcpy it replaces); the Python twin mirrors it
    bit-exactly. Draws: y0, x0, flip — in that order — from
    ``Rng(_aug_seed(seed, epoch, index))``.
    """

    in_shape: tuple[int, int, int]   # (h, w, c) as stored
    crop: tuple[int, int]            # (crop_h, crop_w) as trained
    hflip: bool = True

    def __post_init__(self):
        h, w, c = self.in_shape
        ch, cw = self.crop
        if not (0 < ch <= h and 0 < cw <= w and c > 0):
            raise ValueError(
                f"crop {self.crop} must fit inside in_shape {self.in_shape}")

    @property
    def image_bytes_in(self) -> int:
        h, w, c = self.in_shape
        return h * w * c

    def out_fields(self, fields: "Sequence[Field]") -> list["Field"]:
        """The batch layout after augmentation: the leading image field
        shrinks to the crop; everything after it passes through."""
        img = fields[0]
        if img.dtype != np.uint8 or tuple(img.shape) != self.in_shape:
            raise ValueError(
                f"augmentation needs a leading uint8 image field of shape "
                f"{self.in_shape}; got {img.dtype} {img.shape}")
        ch, cw = self.crop
        return [Field(img.name, img.dtype, (ch, cw, self.in_shape[2])),
                *fields[1:]]

    def apply_one(self, record: np.ndarray, rng: "_Xoshiro256ss") -> np.ndarray:
        """Python-twin augmentation of one packed record (uint8 row)."""
        h, w, c = self.in_shape
        ch, cw = self.crop
        img = record[: h * w * c].reshape(h, w, c)
        y0 = rng.bounded(h - ch + 1)
        x0 = rng.bounded(w - cw + 1)
        flip = self.hflip and (rng.next() & 1)
        crop = img[y0:y0 + ch, x0:x0 + cw]
        if flip:
            crop = crop[:, ::-1]
        return np.concatenate(
            [np.ascontiguousarray(crop).reshape(-1), record[h * w * c:]])


# -- record/field plumbing ---------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Field:
    name: str
    dtype: np.dtype
    shape: tuple[int, ...]

    @property
    def nbytes(self) -> int:
        return int(np.dtype(self.dtype).itemsize * np.prod(self.shape or (1,)))


def make_fields(spec: Mapping[str, tuple]) -> list[Field]:
    """spec: name -> (dtype, shape). Order defines the packed layout."""
    return [Field(n, np.dtype(d), tuple(s)) for n, (d, s) in spec.items()]


def record_bytes(fields: Sequence[Field]) -> int:
    return sum(f.nbytes for f in fields)


def write_records(path: str | Path, columns: Mapping[str, np.ndarray],
                  fields: Sequence[Field], *, append: bool = False) -> int:
    """Pack columns (leading dim = record index) into the flat record file.

    ``append=True`` extends an existing file (records are headerless and
    fixed-size, so concatenation is the file format's only structure) —
    lets large datasets be written in bounded-memory chunks without
    round-tripping each chunk through a temp file.
    """
    n = len(next(iter(columns.values())))
    rb = record_bytes(fields)
    buf = np.zeros((n, rb), np.uint8)
    off = 0
    for f in fields:
        col = np.ascontiguousarray(columns[f.name], dtype=f.dtype)
        if len(col) != n:
            raise ValueError(f"column {f.name} length {len(col)} != {n}")
        flat = col.reshape(n, -1).view(np.uint8).reshape(n, f.nbytes)
        buf[:, off:off + f.nbytes] = flat
        off += f.nbytes
    if append:
        # The format is headerless fixed-size records: appending with a
        # different field layout would silently interleave two record sizes
        # and only surface as garbled batches much later. The only check the
        # format admits is that the existing bytes are a whole number of
        # *this* layout's records — refuse loudly otherwise.
        try:
            existing = os.path.getsize(path)
        except OSError:
            existing = 0  # no file yet: append degenerates to a fresh write
        if existing % rb:
            raise ValueError(
                f"append to {path}: existing size {existing} is not a "
                f"multiple of record_bytes={rb} — field layout mismatch?")
    with open(path, "ab" if append else "wb") as fh:
        fh.write(buf.tobytes())
    return n


def _split_batch(raw: np.ndarray, fields: Sequence[Field]) -> dict:
    """raw (B, record_bytes) uint8 -> {name: (B, *shape) typed array}."""
    out = {}
    off = 0
    b = raw.shape[0]
    for f in fields:
        chunk = raw[:, off:off + f.nbytes]
        out[f.name] = np.ascontiguousarray(chunk).view(f.dtype).reshape(
            (b,) + f.shape)
        off += f.nbytes
    return out


# -- loaders -----------------------------------------------------------------


class NativeRecordLoader:
    """Iterator of field-dict batches backed by the C++ prefetch ring."""

    def __init__(self, path: str | Path, fields: Sequence[Field],
                 batch_size: int, *, shard_id: int = 0, num_shards: int = 1,
                 shuffle: bool = True, seed: int = 0, prefetch: int = 4,
                 n_threads: int = 4, augment: ImageAugment | None = None):
        self.fields = list(fields)
        self.batch_size = batch_size
        self._rb = record_bytes(self.fields)
        lib = load_native_lib()
        if lib is None:
            raise NativeLoaderUnavailable(
                "native loader unavailable; use PyRecordLoader")
        self._lib = lib
        if augment is None:
            self._h = lib.dl_open(str(path).encode(), self._rb, batch_size,
                                  shard_id, num_shards, prefetch, n_threads,
                                  ctypes.c_uint64(seed & MASK64),
                                  int(shuffle))
        else:
            self.fields = augment.out_fields(self.fields)  # batch layout
            h, w, c = augment.in_shape
            ch, cw = augment.crop
            self._h = lib.dl_open_aug(
                str(path).encode(), self._rb, batch_size, shard_id,
                num_shards, prefetch, n_threads,
                ctypes.c_uint64(seed & MASK64), int(shuffle),
                h, w, c, ch, cw, self._rb - augment.image_bytes_in,
                int(augment.hflip))
        if not self._h:
            raise ValueError(
                f"dl_open failed for {path} (record_bytes={self._rb}, "
                f"batch={batch_size}, shard {shard_id}/{num_shards} — file "
                "must be a whole number of records and >= one batch/shard)")
        if augment is not None:
            self._rb = int(lib.dl_record_bytes_out(self._h))
            if self._rb != record_bytes(self.fields):
                # Cross-language layout check (C++ out_record_bytes vs the
                # Python out-field view) — a real ValueError, not an assert:
                # under -O a silent mismatch here would reinterpret
                # misaligned bytes into garbled arrays much later.
                raise ValueError(
                    f"native loader out-record size {self._rb} != Python "
                    f"field layout {record_bytes(self.fields)} bytes")
        self._buf = ctypes.create_string_buffer(batch_size * self._rb)

    @property
    def batches_per_epoch(self) -> int:
        return int(self._lib.dl_batches_per_epoch(self._h))

    @property
    def num_records(self) -> int:
        return int(self._lib.dl_num_records(self._h))

    def __iter__(self) -> Iterator[dict]:
        while True:
            yield self.next_batch()

    def next_batch(self) -> dict:
        seq = self._lib.dl_next(self._h, self._buf)
        if seq < 0:
            raise RuntimeError("dl_next failed")
        raw = np.frombuffer(self._buf, np.uint8).reshape(
            self.batch_size, self._rb).copy()
        return _split_batch(raw, self.fields)

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.dl_close(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            # Interpreter-shutdown teardown: the ctypes lib handle or its
            # globals may already be torn down when GC runs us, and raising
            # from __del__ only prints noise it is too late to act on. The
            # OS reclaims the mmap/threads either way; an explicit close()
            # during normal operation still propagates errors.
            pass


class PyRecordLoader:
    """Pure-Python twin: same files, same order, no threads. Oracle for the
    native loader's tests and fallback when g++ is missing."""

    def __init__(self, path: str | Path, fields: Sequence[Field],
                 batch_size: int, *, shard_id: int = 0, num_shards: int = 1,
                 shuffle: bool = True, seed: int = 0,
                 augment: ImageAugment | None = None):
        self.fields = list(fields)
        self.batch_size = batch_size
        self._rb = record_bytes(self.fields)
        self.augment = augment
        if augment is not None:
            self.fields = augment.out_fields(self.fields)
        data = np.fromfile(str(path), np.uint8)
        if data.size == 0 or data.size % self._rb:
            raise ValueError(f"{path}: not a whole number of records")
        self._records = data.reshape(-1, self._rb)
        self.num_records = len(self._records)
        self.shard_id, self.num_shards = shard_id, num_shards
        self.shuffle, self.seed = shuffle, seed
        self._epoch = -1
        self._indices: np.ndarray | None = None
        self._advance_epoch()
        if self.batches_per_epoch == 0:
            raise ValueError("shard smaller than one batch")
        self._pos = 0

    def _advance_epoch(self) -> None:
        self._epoch += 1
        shard_len = self.num_records // self.num_shards
        if self.shuffle:
            perm = epoch_permutation(self.num_records, self.seed, self._epoch)
            self._indices = perm[self.shard_id * shard_len:
                                 (self.shard_id + 1) * shard_len]
        else:
            self._indices = np.arange(self.shard_id * shard_len,
                                      (self.shard_id + 1) * shard_len)
        self.batches_per_epoch = shard_len // self.batch_size
        self._pos = 0

    def __iter__(self) -> Iterator[dict]:
        while True:
            yield self.next_batch()

    def next_batch(self) -> dict:
        if self._pos >= self.batches_per_epoch:
            self._advance_epoch()
        idx = self._indices[self._pos * self.batch_size:
                            (self._pos + 1) * self.batch_size]
        self._pos += 1
        raw = self._records[idx]
        if self.augment is not None:
            raw = np.stack([
                self.augment.apply_one(
                    raw[r],
                    _Xoshiro256ss(_aug_seed(self.seed, self._epoch,
                                            int(idx[r]))),
                )
                for r in range(raw.shape[0])
            ])
        return _split_batch(raw, self.fields)

    def close(self) -> None:
        # Interface parity with NativeRecordLoader only: the Python twin
        # holds no native handle, threads, or mmap — nothing to release.
        pass


def open_record_loader(path, fields, batch_size, **kw):
    """Native if a toolchain exists, Python twin otherwise."""
    try:
        return NativeRecordLoader(path, fields, batch_size, **kw)
    except NativeLoaderUnavailable:
        kw.pop("prefetch", None)
        kw.pop("n_threads", None)
        return PyRecordLoader(path, fields, batch_size, **kw)
