"""One place decides Pallas interpret mode: CPU interprets, TPU compiles,
anything else refuses (and so do the paths that consult it)."""
from __future__ import annotations

import numpy as np
import pytest

import jax

from repro.kernels import interpret_mode


def test_cpu_interprets():
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True


def test_tpu_compiles_natively(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False


@pytest.mark.parametrize("backend", ["gpu", "cuda", "rocm", "metal"])
def test_unknown_backend_refused(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(RuntimeError, match=backend):
        interpret_mode()


def test_default_paths_consult_it(monkeypatch):
    """The fused histogram and the Pallas sweep take the helper's
    answer when no explicit ``interpret`` is given."""
    from repro.api.batched import SweepGeometry, pack_profile_device, sweep_grid
    from repro.core.reuse.fused import FusedReuseHistogram
    from repro.core.reuse.profile import profile_from_distances

    assert FusedReuseHistogram().interpret is True
    prof = pack_profile_device(profile_from_distances(np.array([-1, 0, 3])))
    ones = np.ones((1, 1), np.float32)
    geom = SweepGeometry(assoc=ones * 2, blocks=ones * 8, trans_beta=ones,
                         delta=ones, cores=np.ones(1, np.float32))
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        FusedReuseHistogram()
    with pytest.raises(RuntimeError, match="gpu"):
        sweep_grid(prof, prof, geom, shared_idx=0, inner="pallas")
