"""On-disk dataset readers (MNIST idx / CIFAR-10 binary / image folder):
fixtures are generated offline in the exact upstream formats, then real
models train from them end to end (VERDICT.md round-1 Missing #3)."""

import gzip
import struct

import numpy as np
import pytest

from pytorch_distributed_nn_tpu.data.datasets import (
    EVAL_STEP_OFFSET,
    get_dataset,
)


# ---------------------------------------------------------------------
# fixture writers — byte-exact upstream formats
# ---------------------------------------------------------------------

def write_idx(path, arr: np.ndarray, *, compress=False):
    code = {np.dtype(np.uint8): 0x08, np.dtype(np.int32): 0x0C}[arr.dtype]
    head = struct.pack(">HBB", 0, code, arr.ndim)
    head += struct.pack(f">{arr.ndim}I", *arr.shape)
    payload = head + arr.astype(arr.dtype.newbyteorder(">")).tobytes()
    if compress:
        path = str(path) + ".gz"
        with gzip.open(path, "wb") as f:
            f.write(payload)
    else:
        with open(path, "wb") as f:
            f.write(payload)


def mnist_dir(tmp_path, *, n_train=256, n_test=64, compress=False):
    rng = np.random.default_rng(0)
    y = (np.arange(n_train) % 10).astype(np.uint8)
    x = (rng.integers(0, 256, (n_train, 28, 28))).astype(np.uint8)
    # class-dependent stripe so tiny models genuinely learn
    for i, yi in enumerate(y):
        x[i, yi * 2:yi * 2 + 3, :] = 255
    write_idx(tmp_path / "train-images-idx3-ubyte", x, compress=compress)
    write_idx(tmp_path / "train-labels-idx1-ubyte", y, compress=compress)
    ty = (np.arange(n_test) % 10).astype(np.uint8)
    tx = (rng.integers(0, 256, (n_test, 28, 28))).astype(np.uint8)
    for i, yi in enumerate(ty):
        tx[i, yi * 2:yi * 2 + 3, :] = 255
    write_idx(tmp_path / "t10k-images-idx3-ubyte", tx, compress=compress)
    write_idx(tmp_path / "t10k-labels-idx1-ubyte", ty, compress=compress)
    return tmp_path


def cifar_dir(tmp_path, *, n_per_batch=64, n_batches=2, n_test=32):
    rng = np.random.default_rng(1)

    def records(n, seed_off):
        y = (np.arange(n) % 10).astype(np.uint8)
        x = rng.integers(0, 256, (n, 3, 32, 32)).astype(np.uint8)
        for i, yi in enumerate(y):
            x[i, :, yi:yi + 3, :] = 255  # learnable stripe (CHW)
        return np.concatenate([y[:, None], x.reshape(n, -1)], 1)

    for b in range(n_batches):
        (tmp_path / f"data_batch_{b + 1}.bin").write_bytes(
            records(n_per_batch, b).tobytes())
    (tmp_path / "test_batch.bin").write_bytes(
        records(n_test, 99).tobytes())
    return tmp_path


def image_folder(tmp_path, *, n_per_class=8, classes=("cat", "dog"),
                 size=40):
    from PIL import Image

    rng = np.random.default_rng(2)
    for ci, cname in enumerate(sorted(classes)):
        d = tmp_path / cname
        d.mkdir()
        for i in range(n_per_class):
            arr = rng.integers(0, 256, (size, size, 3)).astype(np.uint8)
            arr[:, ci * 10:ci * 10 + 8] = 255  # class stripe
            Image.fromarray(arr).save(d / f"img_{i:03d}.png")
    return tmp_path


# ---------------------------------------------------------------------
# format round-trips + split semantics
# ---------------------------------------------------------------------

@pytest.mark.parametrize("compress", [False, True])
def test_mnist_idx_reads_and_splits(tmp_path, compress):
    mnist_dir(tmp_path, compress=compress)
    ds = get_dataset("mnist_idx", seed=0, batch_size=16,
                     path=str(tmp_path))
    x, y = ds.batch(0)
    assert x.shape == (16, 28, 28) and x.dtype == np.float32
    assert 0.0 <= x.min() and x.max() <= 1.0
    assert ds.spec.num_classes == 10
    # the t10k pair is the eval stream: train rows never include it
    assert len(ds._train_rows) == 256 and len(ds._eval_rows) == 64
    xe, ye = ds.batch(EVAL_STEP_OFFSET)
    assert xe.shape == (16, 28, 28)
    # determinism across instances
    ds2 = get_dataset("mnist_idx", seed=0, batch_size=16,
                      path=str(tmp_path))
    np.testing.assert_array_equal(x, ds2.batch(0)[0])


def test_cifar10_bin_reads_and_splits(tmp_path):
    cifar_dir(tmp_path)
    ds = get_dataset("cifar10_bin", seed=0, batch_size=8,
                     path=str(tmp_path))
    x, y = ds.batch(3)
    assert x.shape == (8, 32, 32, 3) and x.dtype == np.float32
    assert len(ds._train_rows) == 128 and len(ds._eval_rows) == 32
    # CHW -> HWC by pixel VALUE: the fixture writes a saturated stripe
    # at CHW rows [y, y+3) across all channels; a correct transpose
    # shows it as HWC rows [y, y+3) == 1.0 everywhere
    for xi, yi in zip(x, y):
        stripe = xi[yi:yi + 3, :, :]
        np.testing.assert_array_equal(stripe, np.ones_like(stripe))


def test_image_folder_reads_lazily(tmp_path):
    image_folder(tmp_path)
    ds = get_dataset("image_folder", seed=0, batch_size=4,
                     path=str(tmp_path), image_size=32)
    assert ds.classes == ["cat", "dog"]
    x, y = ds.batch(0)
    assert x.shape == (4, 32, 32, 3) and x.dtype == np.float32
    assert set(np.unique(ds.y)) == {0, 1}
    # epoch-shuffle coverage: one epoch (16 imgs / batch 4) visits every
    # file exactly once
    seen = []
    for s in range(4):
        idx_batch = ds.batch(s)
        seen.extend(idx_batch[1].tolist())
    assert len(ds.x) == 16 and len(seen) == 16
    assert sorted(np.bincount(seen)) == [8, 8]  # 8 of each class


def test_image_folder_train_val_split(tmp_path):
    (tmp_path / "train").mkdir()
    (tmp_path / "val").mkdir()
    image_folder(tmp_path / "train", n_per_class=8)
    image_folder(tmp_path / "val", n_per_class=2)
    ds = get_dataset("image_folder", seed=0, batch_size=4,
                     path=str(tmp_path), image_size=32)
    assert len(ds._train_rows) == 16 and len(ds._eval_rows) == 4


def test_bad_files_fail_loudly(tmp_path):
    (tmp_path / "train-images-idx3-ubyte").write_bytes(b"junkjunk")
    with pytest.raises(ValueError, match="idx"):
        get_dataset("mnist_idx", seed=0, batch_size=4,
                    path=str(tmp_path))
    with pytest.raises(ValueError, match="data_batch"):
        get_dataset("cifar10_bin", seed=0, batch_size=4,
                    path=str(tmp_path))


def test_read_idx_multibyte_big_endian(tmp_path):
    # idx stores int32 big-endian; a wrong decode returns byte-swapped
    # values (1 -> 16777216)
    from pytorch_distributed_nn_tpu.data.readers import read_idx

    arr = np.array([1, 2, 3], np.int32)
    write_idx(tmp_path / "vals-idx1-int", arr)
    got = read_idx(tmp_path / "vals-idx1-int")
    np.testing.assert_array_equal(got, arr)
    assert got.dtype == np.int32


# ---------------------------------------------------------------------
# end-to-end: real models train from the real on-disk formats
# ---------------------------------------------------------------------

def _train(cfg_overrides, tmp_dir):
    from pytorch_distributed_nn_tpu.config import get_config
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    cfg = get_config("mlp_mnist", **{"log_every": "1",
                                     "data.prefetch": "0"})
    for k, v in cfg_overrides.items():
        parts = k.split(".")
        obj = cfg
        for p in parts[:-1]:
            obj = getattr(obj, p)
        setattr(obj, parts[-1], v)
    cfg.data.path = str(tmp_dir)
    trainer = Trainer(cfg)
    trainer.train()
    return trainer


def test_mlp_trains_from_mnist_idx(tmp_path):
    mnist_dir(tmp_path)
    t = _train({"data.dataset": "mnist_idx", "data.batch_size": 32,
                "steps": 30, "optim.lr": 0.1}, tmp_path)
    losses = t.losses()
    assert losses[-1] < losses[0] * 0.8  # genuinely learns the stripes
    rec = t.evaluate(num_batches=2)  # from the real t10k split
    assert np.isfinite(rec.loss)


def test_lenet_trains_from_cifar10_bin(tmp_path):
    cifar_dir(tmp_path)
    t = _train({"data.dataset": "cifar10_bin", "model.name": "lenet",
                "data.batch_size": 32, "steps": 20,
                "optim.lr": 0.05}, tmp_path)
    losses = t.losses()
    assert losses[-1] < losses[0]


def test_resnet_trains_from_image_folder(tmp_path):
    image_folder(tmp_path, n_per_class=8, size=40)
    t = _train({"data.dataset": "image_folder", "model.name": "resnet50",
                "data.batch_size": 8, "data.image_size": 32,
                "steps": 2, "model.compute_dtype": "float32"}, tmp_path)
    assert np.isfinite(t.losses()).all()


def test_mnist_half_present_t10k_pair_rejected(tmp_path):
    mnist_dir(tmp_path, n_train=32, n_test=16)
    (tmp_path / "t10k-labels-idx1-ubyte").unlink()
    with pytest.raises(ValueError, match="t10k pair incomplete"):
        get_dataset("mnist_idx", seed=0, batch_size=4,
                    path=str(tmp_path))


def test_image_folder_worker_pool_matches_serial(tmp_path):
    """num_workers > 1 must be a pure throughput knob: identical
    batches (order AND pixels) to the inline decode."""
    image_folder(tmp_path)
    kw = dict(seed=0, batch_size=4, path=str(tmp_path), image_size=32)
    serial = get_dataset("image_folder", num_workers=0, **kw)
    pooled = get_dataset("image_folder", num_workers=4, **kw)
    for step in range(3):
        xs, ys = serial.batch(step)
        xp, yp = pooled.batch(step)
        np.testing.assert_array_equal(xs, xp)
        np.testing.assert_array_equal(ys, yp)


def test_image_folder_workers_decode_concurrently(tmp_path, monkeypatch):
    """The pool genuinely overlaps decodes: with a decode stub that
    sleeps (releasing the GIL, like libjpeg's decompress loop), N
    workers must cut batch latency ~N-fold even on one core. This is
    the structural half of the scaling proof; the arithmetic half
    (samples/s/core) is a chip host's to measure."""
    import time as _time

    from pytorch_distributed_nn_tpu.data import readers

    image_folder(tmp_path)
    delay = 0.05

    def slow_decode(self, path):
        _time.sleep(delay)
        return np.zeros((32, 32, 3), np.float32)

    monkeypatch.setattr(readers.ImageFolderDataset, "_decode",
                        slow_decode)
    kw = dict(seed=0, batch_size=8, path=str(tmp_path), image_size=32)

    def batch_time(workers):
        ds = get_dataset("image_folder", num_workers=workers, **kw)
        ds.batch(0)  # warm the pool
        t0 = _time.perf_counter()
        ds.batch(1)
        return _time.perf_counter() - t0

    t_serial = batch_time(0)
    t_pool = batch_time(8)
    assert t_serial > 8 * delay * 0.9  # sanity: serial really serial
    # 8 sleeps over 8 workers ~ 1 slot; allow generous scheduler slack
    assert t_pool < t_serial / 3, (t_serial, t_pool)
