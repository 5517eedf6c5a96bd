"""Dataset loader behaviors a convergence run depends on.

Knowing whether a run trained on synthetic or real data
rests on these: cache discovery finds pre-seeded IDX files, and the
network-guarded fetch NEVER raises on hermetic machines.
"""

import gzip
import pytest
import struct

import numpy as np

import distributed_tpu as dtpu
from distributed_tpu.data import datasets


def _write_idx(path, arr):
    arr = np.ascontiguousarray(arr, np.uint8)
    code = {1: 0x08}[arr.dtype.itemsize]
    header = struct.pack(f">I{arr.ndim}I", (code << 8) | arr.ndim,
                         *arr.shape)
    with gzip.open(path, "wb") as f:
        f.write(header + arr.tobytes())


@pytest.mark.smoke
def test_fetch_mnist_returns_none_without_network(tmp_path, monkeypatch):
    """No egress (this CI) -> None quickly, no exception, no partial files
    left behind."""
    monkeypatch.setattr(datasets, "_MNIST_MIRRORS",
                        ("http://127.0.0.1:1/nope/",))
    # port 1 refuses instantly, so the egress probe and the (unreached)
    # urlopen path are both exercised without a real network
    out = dtpu.data.fetch_mnist(dest_dir=tmp_path / "cache", timeout=0.5)
    assert out is None
    leftover = list((tmp_path / "cache").glob("*")) if (
        tmp_path / "cache").exists() else []
    assert leftover == []


def test_fetch_mnist_short_circuits_on_complete_cache(tmp_path):
    d = tmp_path / "mnist"
    d.mkdir()
    for fname in datasets._MNIST_FILES:
        shape = datasets._MNIST_SHAPES[fname]
        _write_idx(d / fname, np.zeros(shape, np.uint8))
    assert dtpu.data.fetch_mnist(dest_dir=d) == d


def test_fetch_mnist_rejects_checksum_mismatch(tmp_path, monkeypatch):
    """A mirror serving altered-but-valid-looking IDX bytes is rejected by
    the pinned digests before anything lands in the cache (ADVICE r4)."""
    import io
    import urllib.request

    # Make the egress probe think the (fake) mirror is reachable.
    import socket

    class _Conn:
        def close(self):
            pass

    monkeypatch.setattr(socket, "create_connection",
                        lambda *a, **k: _Conn())

    # Serve structurally-valid IDX payloads (magic + shape pass) whose
    # bytes differ from the canonical files -> md5 mismatch.
    def fake_urlopen(url, timeout=None):
        fname = url.rsplit("/", 1)[1]
        shape = datasets._MNIST_SHAPES[fname]
        arr = np.zeros(shape, np.uint8)
        code = 0x08
        header = struct.pack(f">I{arr.ndim}I", (code << 8) | arr.ndim,
                             *arr.shape)
        buf = io.BytesIO()
        with gzip.GzipFile(fileobj=buf, mode="wb") as f:
            f.write(header + arr.tobytes())
        body = buf.getvalue()

        class _Resp:
            def read(self, n=-1):
                return body if n < 0 else body[:n]

            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

        return _Resp()

    monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
    monkeypatch.delenv("DTPU_MNIST_NO_CHECKSUM", raising=False)
    out = dtpu.data.fetch_mnist(dest_dir=tmp_path / "cache", timeout=0.5)
    assert out is None
    assert list((tmp_path / "cache").glob("*.gz")) == []


def test_load_digits_real_is_real_and_deterministic():
    """The convergence fallback: real scans, deterministic stratified split,
    train/test disjoint, MNIST-shaped output contract."""
    pytest.importorskip("sklearn")
    x1, y1 = dtpu.data.load_digits_real("train")
    x2, y2 = dtpu.data.load_digits_real("train")
    np.testing.assert_array_equal(x1, x2)  # same seed -> same partition
    np.testing.assert_array_equal(y1, y2)
    xt, yt = dtpu.data.load_digits_real("test")
    assert x1.shape[1:] == (28, 28, 1) and xt.shape[1:] == (28, 28, 1)
    assert x1.dtype == np.float32 and x1.max() <= 1.0  # normalized
    assert len(x1) + len(xt) == 1797  # every real scan used exactly once
    assert set(np.unique(y1)) == set(range(10))
    assert set(np.unique(yt)) == set(range(10))
    # Stratification: each class's test share is ~20%.
    for c in range(10):
        n_tr = int((y1 == c).sum())
        n_te = int((yt == c).sum())
        assert 0.15 <= n_te / (n_tr + n_te) <= 0.25


def test_load_mnist_finds_preseeded_idx_cache(tmp_path, monkeypatch):
    """The provisioning recipe (docs/PROVISIONING.md): IDX .gz files under
    $DTPU_DATA_DIR/mnist are found and parsed, bypassing synthetic."""
    d = tmp_path / "mnist"
    d.mkdir()
    rng = np.random.default_rng(0)
    x = rng.integers(0, 255, (64, 28, 28), dtype=np.uint8)
    y = rng.integers(0, 10, (64,), dtype=np.uint8)
    _write_idx(d / "train-images-idx3-ubyte.gz", x)
    _write_idx(d / "train-labels-idx1-ubyte.gz", y)
    # Patch the search path wholesale: a real mnist.npz in this user's
    # ~/.keras/datasets would otherwise shadow the fixture.
    monkeypatch.setattr(datasets, "_search_dirs", lambda dd: [tmp_path])
    got_x, got_y = dtpu.data.load_mnist("train", synthetic_ok=False,
                                        normalize=False)
    np.testing.assert_array_equal(got_x[..., 0], x)
    np.testing.assert_array_equal(got_y, y.astype(np.int32))
