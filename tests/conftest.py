"""Test env: CPU backend with 8 virtual devices for sharding tests.

Reference test strategy (SURVEY.md §4): distributed tests run N processes on
localhost sockets (tests/distributed/_test_distributed.py). The TPU-native
equivalent is a virtual multi-device CPU mesh — same collectives, no pod.
"""

from lightgbm_tpu.parallel.mesh import provision_virtual_devices

provision_virtual_devices(8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_programs_between_modules():
    """Tier-1 is one process. With every module's executables kept
    alive, XLA:CPU died (SIGSEGV/abort in backend_compile_and_load) some
    370 tests in; dropping them between modules lets the run finish,
    and faster than it got to the crash."""
    yield
    import jax
    jax.clear_caches()


@pytest.fixture
def low_crossover(monkeypatch):
    """hist_backend=auto's per-pass rule (histogram_pallas.use_grouped)
    with its constants lowered, so that it engages at sizes interpret
    mode can run: the one-hot/grouped choice then differs between the
    passes of ONE small tree. The constants are read at trace time, so
    compiled programs are dropped around the patch."""
    import jax
    from lightgbm_tpu.learner import histogram_pallas as hp
    jax.clear_caches()
    monkeypatch.setattr(hp, "GROUPED_MIN_WIDTH", 20)
    monkeypatch.setattr(hp, "GROUPED_MIN_ROWS_PER_PAD", 0)
    yield
    jax.clear_caches()


def make_binary(n=2000, f=10, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    logit = X[:, 0] * 1.5 + 0.5 * X[:, 1] ** 2 - X[:, 2] + 0.3 * r.randn(n)
    y = (logit > np.median(logit)).astype(np.float32)
    return X, y


def make_regression(n=2000, f=10, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    y = (X[:, 0] * 2 + X[:, 1] ** 2 - 0.5 * X[:, 2] +
         0.1 * r.randn(n)).astype(np.float32)
    return X, y


def make_multiclass(n=3000, f=10, k=4, seed=0):
    r = np.random.RandomState(seed)
    X = r.randn(n, f)
    centers = r.randn(k, f) * 2
    logits = X @ centers.T + 0.5 * r.randn(n, k)
    y = logits.argmax(1).astype(np.float32)
    return X, y


def make_ranking(num_queries=100, docs_per_query=20, f=10, seed=0):
    r = np.random.RandomState(seed)
    n = num_queries * docs_per_query
    X = r.randn(n, f)
    rel = X[:, 0] + 0.5 * X[:, 1] + 0.5 * r.randn(n)
    # map to 0-4 labels by quantile
    qs = np.quantile(rel, [0.5, 0.75, 0.9, 0.97])
    y = np.digitize(rel, qs).astype(np.float32)
    group = np.full(num_queries, docs_per_query)
    return X, y, group
