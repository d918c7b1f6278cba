import os
import sys

import pytest

# NOTE: deliberately NO xla_force_host_platform_device_count here — smoke
# tests and benches must see the single real device (the 512-device flag
# belongs to launch/dryrun.py only).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def interpreted_knn_kernel(monkeypatch):
    """Wherever the program calls the Pallas K-NN kernel, run it through
    the Pallas interpreter: the CPU cannot compile the kernel.  The jit
    caches, which now hold interpreted traces, are dropped afterwards."""
    import jax

    import repro.kernels.knn_topk as knn_topk
    compiled = knn_topk.row_top2_regret
    monkeypatch.setattr(knn_topk, "row_top2_regret",
                        lambda proto, **kw: compiled(
                            proto, **{**kw, "interpret": True}))
    yield
    jax.clear_caches()
