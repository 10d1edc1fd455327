"""``model/<arch>/<step>`` workloads: HLO-derived labeled traces.

``ModelTraceSource`` lowers one model step (prefill / decode / train of
a ``configs/`` architecture at its reduced smoke shape) to optimized
HLO with plain ``jax.jit`` on abstract operands — no mesh, no device
allocation — then feeds the text through the existing offline
analyzers: ``analysis/hlo_trace`` emits the granule-labeled memory
trace (entry parameters = weights = shared across mimicked cores),
``analysis/hlo_cost`` supplies Byfl-style OpCounts for the runtime
model, and ``analysis/buffers`` records the liveness-dominating
buffers for provenance.

Lowering is the expensive step (~2s per cell), so everything derived
from it is persisted in the ArtifactStore's ``workload`` kind keyed by
the declared fingerprint: a warm store answers ``op_counts`` and
``refs`` without ever invoking XLA, and the Session only materializes
the trace on a profile-store miss.

XLA's scheduling is deterministic for a fixed (jaxlib, config, shape)
tuple — the same cell lowers to bit-identical HLO across processes —
which is what lets a *declared* fingerprint stand in for the trace
content hash.  ``jax.__version__`` and the lowering platform are folded
into the fingerprint so a toolchain upgrade or a move between CPU and
TPU invalidates cleanly, and
``Session(verify_fingerprints=True)`` cross-checks the recorded
``trace_content_id`` whenever the trace is rebuilt.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

if TYPE_CHECKING:  # pragma: no cover
    from repro.core.trace.types import LabeledTrace

# Bump when lowering or trace extraction changes trace content for the
# same (arch, step) — declared fingerprints hash this.  "2": op_counts
# now carry the per-class op_class_mix (int/load/store split), and the
# store's workload meta must not serve the old fp/loads-only counts.
# "3": the fingerprint names the platform the HLO is compiled for.
MODEL_TRACE_VERSION = "3"

STEPS = ("prefill", "decode", "train")

# HLO granule / cap defaults — chosen so smoke-shape steps stay in the
# few-thousand-reference regime the validation harness expects.
GRANULE = 512
REFS_CAP = 16
LOOP_CAP = 2


def arch_slug(arch_id: str) -> str:
    return arch_id.replace("-", "_").replace(".", "_")


class ModelTraceSource:
    """TraceSource for one (arch, step) cell.

    Satisfies the stage-1 protocol (``trace()``) plus the registry's
    declared-source extensions (``workload_name`` /
    ``declared_fingerprint`` attrs set by ``resolve()``,
    ``attach_store`` for warm-path metadata).
    """

    def __init__(self, arch_id: str, step: str, *, granule: int = GRANULE,
                 refs_cap: int = REFS_CAP, loop_cap: int = LOOP_CAP):
        if step not in STEPS:
            raise ValueError(f"unknown model step {step!r} (one of {STEPS})")
        self.arch_id = arch_id
        self.step = step
        self.granule = granule
        self.refs_cap = refs_cap
        self.loop_cap = loop_cap
        self.workload_name = f"model/{arch_slug(arch_id)}/{step}"
        self.declared_fingerprint: str | None = None
        self._store = None
        self._trace = None
        self._op_counts = None
        self._info: dict | None = None

    # --- registry/store integration ---------------------------------------

    def attach_store(self, store) -> None:
        self._store = store

    def _store_meta(self) -> dict | None:
        if self._store is None or not self.declared_fingerprint:
            return None
        return self._store.get_json("workload", self.declared_fingerprint)

    def _put_store_meta(self, meta: dict) -> None:
        if self._store is None or not self.declared_fingerprint:
            return
        merged = dict(self._store_meta() or {})
        merged.update(meta)
        self._store.put_json("workload", self.declared_fingerprint, merged)

    # --- lowering ----------------------------------------------------------

    def lowered_hlo(self) -> str:
        """Optimized HLO text of the step (compiles the cell)."""
        import jax
        import jax.numpy as jnp

        from repro.configs.reduced import (
            SMOKE_DECODE, SMOKE_PREFILL, SMOKE_SHAPE, reduced_arch,
        )
        from repro.models.layers import unzip_params

        spec = reduced_arch(self.arch_id)
        fam, cfg = spec.family, spec.config
        shape = {"train": SMOKE_SHAPE, "prefill": SMOKE_PREFILL,
                 "decode": SMOKE_DECODE}[self.step]
        aparams, _ = unzip_params(jax.eval_shape(
            lambda k: fam.init(k, cfg), jax.random.key(0)
        ))
        batch = spec.input_specs(shape)

        if self.step == "train":
            def fn(p, b):
                return jax.value_and_grad(lambda q: fam.loss_fn(q, b, cfg))(p)
            args = (aparams, batch)
        else:
            acaches = jax.eval_shape(
                lambda: fam.init_caches(cfg, **spec.cache_kwargs(shape))
            )
            if self.step == "prefill":
                def fn(p, b, c):
                    return fam.prefill(p, b, cfg, c)
                args = (aparams, batch, acaches)
            else:
                def fn(p, b, c, n):
                    return fam.decode_step(p, b, cfg, c, n)
                args = (aparams, batch, acaches,
                        jax.ShapeDtypeStruct((), jnp.int32))
        return jax.jit(fn).lower(*args).compile().as_text()

    def _lower(self) -> None:
        from repro.analysis.buffers import largest_buffers
        from repro.analysis.hlo_cost import loop_aware_cost, op_class_mix
        from repro.analysis.hlo_trace import hlo_to_trace
        from repro.core.runtime_model import OpCounts
        from repro.workloads.tracegen import ELEM

        hlo = self.lowered_hlo()
        trace, info = hlo_to_trace(
            hlo, granule=self.granule, refs_cap=self.refs_cap,
            loop_cap=self.loop_cap,
        )
        cost = loop_aware_cost(hlo)
        # per-class mix (loads/stores split, addressing int ops,
        # transcendental -> div port) — the instruction-aware runtime
        # models need every class populated, not just fp/loads
        self._op_counts = OpCounts(**op_class_mix(cost, elem_bytes=ELEM))
        buffers = largest_buffers(hlo, top=8, min_bytes=0)
        self._info = {
            "touched_bytes": info.get("touched_bytes"),
            "loop_scale": info.get("loop_scale"),
            "num_buffers": info.get("num_buffers"),
            "num_blocks": info.get("num_blocks"),
            "granule": self.granule,
            "top_buffers": [
                {"bytes": b.bytes, "op": b.op, "name": b.name}
                for b in buffers
            ],
        }
        self._trace = trace
        self._put_store_meta({
            "workload": self.workload_name,
            "arch": self.arch_id,
            "step": self.step,
            "refs": len(trace),
            "op_counts": {
                "int_ops": self._op_counts.int_ops,
                "fp_ops": self._op_counts.fp_ops,
                "div_ops": self._op_counts.div_ops,
                "loads": self._op_counts.loads,
                "stores": self._op_counts.stores,
                "total_bytes": self._op_counts.total_bytes,
            },
            **self._info,
        })

    # --- stage-1 protocol ---------------------------------------------------

    def trace(self) -> "LabeledTrace":
        if self._trace is None:
            self._lower()
        return self._trace

    @property
    def op_counts(self):
        """OpCounts for the runtime model; served from the store's
        workload meta when warm (no lowering)."""
        if self._op_counts is None:
            meta = self._store_meta()
            if meta and "op_counts" in meta:
                from repro.core.runtime_model import OpCounts
                self._op_counts = OpCounts(**meta["op_counts"])
            else:
                self._lower()
        return self._op_counts

    @property
    def info(self) -> dict:
        if self._info is None:
            meta = self._store_meta()
            if meta and "touched_bytes" in meta:
                self._info = {k: meta.get(k) for k in (
                    "touched_bytes", "loop_scale", "num_buffers",
                    "num_blocks", "granule", "top_buffers")}
            else:
                self._lower()
        return self._info


def fingerprint_kwargs(arch_id: str, step: str, *, granule: int = GRANULE,
                       refs_cap: int = REFS_CAP,
                       loop_cap: int = LOOP_CAP) -> dict:
    """Everything that pins the trace bytes of a model cell.

    ``platform`` is the backend :meth:`ModelTraceSource.lowered_hlo`
    compiles for: XLA optimizes the same step differently per backend,
    so a CPU-lowered and a TPU-lowered trace must never share a key.
    """
    import jax

    return {
        "arch": arch_id,
        "step": step,
        "granule": granule,
        "refs_cap": refs_cap,
        "loop_cap": loop_cap,
        "model_trace_version": MODEL_TRACE_VERSION,
        "jax": jax.__version__,
        "platform": jax.default_backend(),
    }


def register_model_workloads(registry) -> None:
    """Register model/<slug>/<step> for every configured architecture.

    All size presets resolve to the reduced smoke shapes (the full
    shapes' traces are the dry-run's job), so every preset shares one
    fingerprint and one artifact set per cell.  The raw arch id
    (``model/llama3-8b/decode``) stays routable as an alias wherever
    it differs from the slug.
    """
    from repro.configs import list_archs
    from repro.workloads.registry import WorkloadSpec

    for arch_id in list_archs():
        slug = arch_slug(arch_id)
        for step in STEPS:
            def build(sizes, _arch=arch_id, _step=step):
                return ModelTraceSource(_arch, _step)

            def size_kwargs(sizes, _arch=arch_id, _step=step):
                return fingerprint_kwargs(_arch, _step)

            aliases = ()
            if slug != arch_id:
                aliases = (f"model/{arch_id}/{step}",)
            registry.register(WorkloadSpec(
                name=f"model/{slug}/{step}",
                build=build,
                size_kwargs=size_kwargs,
                presets=("smoke", "validation", "validation-xl",
                         "validation-xxl"),
                aliases=aliases,
                version=MODEL_TRACE_VERSION,
                description=f"{arch_id} {step} step via HLO lowering",
            ))
