import os
import signal
import threading

# Smoke tests and benches must see ONE device; only launch/dryrun.py sets the
# 512-device XLA flag (before importing jax). Guard against env leakage.
os.environ.pop("XLA_FLAGS", None)

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _time_limit(request):
    """Fail a test marked ``time_limit(seconds)`` once it has run that long
    (SIGALRM: the limit holds in the main thread, where pytest runs tests)."""
    mark = request.node.get_closest_marker("time_limit")
    if mark is None \
            or threading.current_thread() is not threading.main_thread():
        yield
        return
    seconds = mark.args[0]

    def expire(signum, frame):
        raise TimeoutError(f"{request.node.name} ran over {seconds} s")
    old = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


def make_qkv(key, b, hq, hkv, sq, skv, d, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, skv, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, skv, d), dtype)
    do = jax.random.normal(ks[3], (b, hq, sq, d), dtype)
    return q, k, v, do


def max_err(a, b):
    return float(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max())
