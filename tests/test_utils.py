"""Utils tests: PhotonLogger, Timed, EventEmitter, linalg helpers."""

import logging
import os

import numpy as np
import pytest

from photon_ml_tpu.utils import (Event, EventEmitter, EventListener,
                                 PhotonLogger, Timed, cholesky_inverse, timed)
from photon_ml_tpu.utils.linalg import solve_psd


class TestLogging:
    def test_photon_logger_writes_file(self, tmp_path):
        path = str(tmp_path / "out" / "log-message.txt")
        with PhotonLogger(path, name="test.photon") as log:
            log.info("phase %s done", "train")
            log.logger.handlers[0].flush()
        with open(path) as f:
            assert "phase train done" in f.read()

    def test_timed_sink(self):
        seen = {}
        with Timed("phase", sink=lambda label, s: seen.update({label: s})):
            pass
        assert "phase" in seen and seen["phase"] >= 0

    def test_timed_decorator(self):
        @timed("work")
        def f(x):
            return x + 1

        assert f(1) == 2


class TestEvents:
    def test_emit_and_listen(self):
        emitter = EventEmitter()
        got = []
        emitter.register(lambda e: got.append(e))
        ev = emitter.emit("training_start", task="logistic")
        assert got == [ev]
        assert got[0].payload["task"] == "logistic"

    def test_register_by_name(self):
        emitter = EventEmitter()
        listener = emitter.register(
            "photon_ml_tpu.utils.events:EventListener")
        assert isinstance(listener, EventListener)
        emitter.close_listeners()


class TestLinalg:
    def test_cholesky_inverse(self, rng):
        a = rng.normal(size=(6, 6))
        spd = a @ a.T + 6 * np.eye(6)
        inv = np.asarray(cholesky_inverse(spd))
        np.testing.assert_allclose(inv, np.linalg.inv(spd), atol=1e-8)

    def test_solve_psd(self, rng):
        a = rng.normal(size=(5, 5))
        spd = a @ a.T + 5 * np.eye(5)
        b = rng.normal(size=5)
        x = np.asarray(solve_psd(spd, b))
        np.testing.assert_allclose(spd @ x, b, atol=1e-8)

    def test_jitter(self):
        near_singular = np.zeros((3, 3))
        inv = np.asarray(cholesky_inverse(near_singular, jitter=1.0))
        np.testing.assert_allclose(inv, np.eye(3), atol=1e-10)


# ---------------------------------------------------------------------------
# Date ranges (reference DateRange.scala / DaysRange.scala / IOUtils:113-153)
# ---------------------------------------------------------------------------

def test_date_range_parsing():
    import datetime

    import pytest

    from photon_ml_tpu.utils.dates import DateRange, DaysRange, resolve_range

    r = DateRange.from_string("20170101-20170105")
    assert r.start == datetime.date(2017, 1, 1)
    assert r.end == datetime.date(2017, 1, 5)
    assert len(r.days()) == 5
    assert str(r) == "20170101-20170105"

    with pytest.raises(ValueError):
        DateRange.from_string("20170105-20170101")  # start after end
    with pytest.raises(ValueError):
        DateRange.from_string("2017-01-01")  # wrong grammar

    d = DaysRange.from_string("90-1")
    today = datetime.date(2017, 4, 11)
    dr = d.to_date_range(today)
    assert dr.start == today - datetime.timedelta(days=90)
    assert dr.end == today - datetime.timedelta(days=1)
    with pytest.raises(ValueError):
        DaysRange.from_string("1-90")  # start must be further back

    with pytest.raises(ValueError):
        resolve_range("20170101-20170105", "90-1")  # mutually exclusive
    assert resolve_range(None, None) is None


def test_input_paths_within_date_range(tmp_path):
    import pytest

    from photon_ml_tpu.utils.dates import DateRange, input_paths_within_date_range

    base = tmp_path / "daily"
    for day in ("2017/01/01", "2017/01/02", "2017/01/04"):
        (base / day).mkdir(parents=True)

    r = DateRange.from_string("20170101-20170105")
    paths = input_paths_within_date_range([str(base)], r)
    assert [p[-10:] for p in paths] == ["2017/01/01", "2017/01/02", "2017/01/04"]

    with pytest.raises(FileNotFoundError):  # Jan 3 missing
        input_paths_within_date_range([str(base)], r, error_on_missing=True)
    with pytest.raises(FileNotFoundError):  # no day at all in range
        input_paths_within_date_range([str(base)], DateRange.from_string(
            "20180101-20180102"))


@pytest.fixture
def cache_config():
    """Restore jax's cache-dir setting after a test moved it."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compilation_cache_default_is_fixed_under_checkout(
        tmp_path, monkeypatch, cache_config):
    """Unset JAX_COMPILATION_CACHE_DIR: the cache is <checkout>/.xla_cache
    (+ a machine-derived tag) — a fixed path, the same in every process."""
    import os

    import jax

    from photon_ml_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = compile_cache._default_dir()
    assert want == compile_cache._default_dir()
    assert os.path.dirname(want) == os.path.join(repo, ".xla_cache")
    monkeypatch.setattr(compile_cache, "_default_dir",
                        lambda: str(tmp_path / "cache"))
    got = compile_cache.enable_compilation_cache()
    assert got == str(tmp_path / "cache") and os.path.isdir(got)
    assert jax.config.jax_compilation_cache_dir == got


def test_compilation_cache_placed_from_outside(tmp_path, monkeypatch,
                                               cache_config):
    """JAX_COMPILATION_CACHE_DIR set: the function sets NO directory in
    code (jax's own setting is left alone), creates none, and reports the
    outside one.  PHOTON_COMPILE_CACHE no longer places or disables
    anything."""
    import jax

    from photon_ml_tpu.utils import compile_cache

    jax.config.update("jax_compilation_cache_dir", "/set/by/jax")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    monkeypatch.setenv("PHOTON_COMPILE_CACHE", str(tmp_path / "old_knob"))
    monkeypatch.setattr(compile_cache, "_default_dir",
                        lambda: str(tmp_path / "default"))
    assert compile_cache.enable_compilation_cache() == str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir == "/set/by/jax"
    assert list(tmp_path.iterdir()) == []
    monkeypatch.setenv("PHOTON_COMPILE_CACHE", "0")  # the old off switch
    assert compile_cache.enable_compilation_cache() == str(tmp_path / "outside")


def test_compilation_cache_that_cannot_be_set_up_raises(tmp_path, monkeypatch,
                                                        cache_config):
    """A cache directory that cannot be created is an error, not a warning:
    an uncached process pays every first-compile again."""
    from photon_ml_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.setattr(compile_cache, "_default_dir",
                        lambda: str(blocker / "cache"))
    with pytest.raises(OSError):
        compile_cache.enable_compilation_cache()


def test_sparse_feature_stats_match_dense():
    """compute_feature_stats_sparse == compute_feature_stats on the densified
    twin (unique indices per row — duplicates are documented-approximate)."""
    import numpy as np

    from photon_ml_tpu.core.normalization import (compute_feature_stats,
                                                  compute_feature_stats_sparse)

    rng = np.random.default_rng(0)
    n, d, k = 500, 40, 6
    idx = np.stack([rng.choice(d - 1, size=k, replace=False)
                    for _ in range(n)]).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    vals[rng.random((n, k)) < 0.2] = 0.0  # padded slots
    # column d-1 observed (nonzero, strictly positive) in EVERY row: its
    # min/max must be the true extremes, not the implicit-zero default
    idx[:, -1] = d - 1
    vals[:, -1] = rng.random(n).astype(np.float32) + 0.5
    w = rng.random(n).astype(np.float32) + 0.5
    dense = np.zeros((n, d), np.float32)
    np.add.at(dense, (np.repeat(np.arange(n), k), idx.ravel()), vals.ravel())
    sd = compute_feature_stats(np.asarray(dense), np.asarray(w), intercept_index=3)
    ss = compute_feature_stats_sparse(idx, vals, d, weight=w, intercept_index=3)
    for f in ("mean", "variance", "abs_max", "num_nonzeros", "min", "max",
              "count"):
        np.testing.assert_allclose(np.asarray(getattr(sd, f)),
                                   np.asarray(getattr(ss, f)),
                                   atol=1e-4, rtol=1e-3, err_msg=f)


class TestDevicePutCounted:
    """Host->device placement of design arrays (utils/transfer.py):
    byte-identical to a direct jnp.asarray, narrowed on the host, counted
    by the probe, one transfer whatever the size."""

    def test_matches_direct_path_and_narrows_on_host(self):
        import numpy as np

        from photon_ml_tpu.utils.transfer import device_put_counted

        rng = np.random.default_rng(0)
        a = rng.normal(size=(1000, 7)).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(device_put_counted(a)), a)
        out16 = device_put_counted(a, "bfloat16")
        assert str(out16.dtype) == "bfloat16"
        np.testing.assert_array_equal(
            np.asarray(out16), np.asarray(a.astype(out16.dtype)))

    def test_one_transfer_whatever_the_size(self, monkeypatch):
        """There is no chunking on a local chip: a large array, a transposed
        narrow one and a small one each cross in exactly ONE jnp.asarray,
        and the old PHOTON_CHUNKED_PUT_MIN_MB knob changes nothing."""
        import numpy as np

        from photon_ml_tpu.utils import transfer

        calls = []
        real = transfer.jnp.asarray

        def counting(a, *args, **kw):
            calls.append(np.shape(a))
            return real(a, *args, **kw)

        monkeypatch.setattr(transfer, "jnp",
                            type("J", (), {"asarray": staticmethod(counting),
                                           "dtype": transfer.jnp.dtype}))
        monkeypatch.setenv("PHOTON_CHUNKED_PUT_MIN_MB", str(1 / 1024))
        big = np.zeros((1000, 7), np.float32)
        wide = np.arange(2 * 5000, dtype=np.float32).reshape(2, 5000)
        for a in (big, wide, np.ones((3, 4), np.float32)):
            np.testing.assert_array_equal(
                np.asarray(transfer.device_put_counted(a)), a)
        assert calls == [(1000, 7), (2, 5000), (3, 4)]

    def test_device_array_passes_through(self):
        """An already-resident array (a streamed shard, one upload shared
        by several coordinates) never round-trips through the host."""
        import jax.numpy as jnp

        from photon_ml_tpu.utils.transfer import device_put_counted

        x = jnp.arange(12.0).reshape(3, 4)
        assert device_put_counted(x) is x
        assert device_put_counted(x, x.dtype) is x
        assert str(device_put_counted(x, "bfloat16").dtype) == "bfloat16"
