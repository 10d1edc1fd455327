"""ModelTraceSource: deterministic HLO-derived traces and store-served
metadata (ISSUE-7 satellite)."""
from __future__ import annotations

import numpy as np
import pytest

from repro.validate.store import ArtifactStore
from repro.workloads import registry as R
from repro.workloads.model_trace import ModelTraceSource, arch_slug

ARCH = "llama3-8b"
NAME = "model/llama3_8b/decode"


def test_arch_slug():
    assert arch_slug("llama3-8b") == "llama3_8b"
    assert arch_slug("zamba2-1.2b") == "zamba2_1_2b"


def test_unknown_step_rejected():
    with pytest.raises(ValueError, match="unknown model step"):
        ModelTraceSource(ARCH, "finetune")


def test_determinism_same_fingerprint_and_bitidentical_trace():
    """Same (config, step): identical declared fingerprint from two
    independent resolutions, and bit-identical traces from two
    independent lowerings."""
    a = R.resolve(NAME, "smoke")
    b = R.resolve("model/llama3-8b/decode", "smoke")   # raw-id alias
    assert a.declared_fingerprint == b.declared_fingerprint
    ta, tb = a.trace(), b.trace()
    np.testing.assert_array_equal(ta.addresses, tb.addresses)
    np.testing.assert_array_equal(ta.bb_ids, tb.bb_ids)
    np.testing.assert_array_equal(ta.shared_mask, tb.shared_mask)
    assert len(ta) > 0
    # entry parameters (weights) are the shared references
    assert ta.shared_mask.any() and not ta.shared_mask.all()


def test_fingerprint_names_the_lowering_backend(monkeypatch):
    """The HLO is compiled for the default backend, and XLA lowers the
    same step differently per backend: a TPU-lowered cell must never be
    served a CPU-lowered trace from a shared store, or the reverse."""
    import jax

    on_cpu = R.declared_fingerprint(NAME, "smoke")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_tpu = R.declared_fingerprint(NAME, "smoke")
    assert on_cpu != on_tpu
    assert R.declared_fingerprint(NAME, "validation") == on_tpu


def test_op_counts_served_from_store_without_lowering(tmp_path):
    """A warm store answers op_counts from workload meta — the second
    source never invokes XLA."""
    store = ArtifactStore(tmp_path)
    first = R.resolve(NAME, "smoke", store=store)
    first.trace()                                    # lowers + persists
    counts = first.op_counts

    fresh = R.resolve(NAME, "smoke", store=store)
    fresh.lowered_hlo = lambda: (_ for _ in ()).throw(
        AssertionError("warm op_counts must not lower")
    )
    assert fresh.op_counts == counts
    assert fresh.info["touched_bytes"] == first.info["touched_bytes"]
    assert counts.fp_ops > 0 and counts.total_bytes > 0


def test_session_verify_fingerprints_cross_check(tmp_path):
    """verify_fingerprints=True recomputes the content hash on
    materialization and raises if it diverges from the recorded one."""
    from repro.api import Session

    store = ArtifactStore(tmp_path)
    w = R.resolve("polybench/atx", "smoke", store=store)
    s = Session(store=store, verify_fingerprints=True)
    tid, trace = s.load(w)                 # records trace_content_id
    meta = store.get_json("workload", tid)
    assert meta["trace_content_id"]

    # poison the recorded hash: a fresh verifying Session must notice
    store.put_json("workload", tid,
                   {**meta, "trace_content_id": "0" * 16})
    s2 = Session(store=store, verify_fingerprints=True)
    w2 = R.resolve("polybench/atx", "smoke", store=store)
    with pytest.raises(RuntimeError, match="stale"):
        s2.load(w2)
