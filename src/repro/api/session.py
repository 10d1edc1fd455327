"""Session: executes PredictionRequests with content-hash artifact
caching.

The paper's headline property — "predictions for various core counts
without having to rerun the application" — becomes an invariant here:
one trace is loaded once, and every derived artifact is cached under
content-hash keys

    reuse distances       (trace_id, line_size)
    mimicked privates     (trace_id, cores)
    interleaved shared    (trace_id, cores, strategy, seed)
    PRD/CRD profiles      (trace_id, line_size, cores, strategy, seed)

so a full (target x core-count x strategy) sweep computes each profile
exactly once across ALL targets (the three Table-5 CPUs share 64-byte
lines; the TPU's 512-byte VMEM granule adds one more profile set, not
a new pipeline).  ``Session.stats`` exposes build/hit counters — tests
assert the compute-once property instead of trusting it.

The in-memory caches are process-local; ``Session(artifact_dir=...)``
(or ``store=ArtifactStore(...)``) transparently layers a disk-backed
store *under* them: a profile missing from memory is loaded from disk
before being rebuilt, and every freshly built profile is written back
— so repeated sweeps are incremental across processes and runs
(``repro.validate.store``).  Lookup order per cell:

    in-memory dict  ->  ArtifactStore (npz on disk)  ->  build + put

``predict_many`` evaluates many independent requests through one
cache-model grid call — the coalescible surface the concurrent
prediction service (:mod:`repro.service`) microbatches through.
"""
from __future__ import annotations

import dataclasses

from repro import telemetry
from repro.api.request import PredictionRequest
from repro.api.results import CellPrediction, PredictionSet
from repro.api.stages import (
    AnalyticalSDCM,
    ExactLRU,
    MimicProfileBuilder,
    ProfileArtifacts,
    as_trace_source,
    default_runtime_model,
    resolve_runtime_model,
    trace_content_id,
)
from repro.core.reuse.profile import profile_from_distances
from repro.core.trace.types import LabeledTrace
from repro.hw.targets import resolve_target


def _materialize(source) -> LabeledTrace:
    """Build a source's trace: the one place a Session generates one."""
    with telemetry.span("workload.trace") as span:
        trace = as_trace_source(source).trace()
        span.count(len(trace))
    return trace


@dataclasses.dataclass
class SessionStats:
    """Observable cache behaviour (asserted by tests/benchmarks)."""

    trace_builds: int = 0
    rd_builds: int = 0
    mimic_builds: int = 0
    interleave_builds: int = 0
    profile_builds: int = 0
    profile_hits: int = 0
    streaming_builds: int = 0
    store_hits: int = 0     # profiles served from the disk store
    store_puts: int = 0     # freshly built profiles written back
    kernel_compiles: int = 0  # NEW jit compile-cache entries this session
    # triggered in `repro.api.batched` (grid + config-sweep kernels).
    # A warm session re-running an identical sweep must leave this
    # unchanged: every dispatch lands on an existing row-shape key.

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)


class Session:
    """Cached executor for :class:`PredictionRequest` grids.

    Stages are injectable: pass a different ``cache_model`` (e.g.
    :class:`repro.api.stages.ExactLRU`) or ``profile_builder`` and the
    same request produces ground-truth or alternative-model grids.
    ``cache=False`` disables artifact reuse (the legacy per-call cost
    model — used by the deprecated shim and the benchmark baseline).

    ``artifact_dir`` (or an explicit ``store``) layers a disk-backed
    :class:`repro.validate.store.ArtifactStore` under the in-memory
    caches: profiles survive the process, so a second run over the
    same traces performs zero reuse-profile recomputations
    (``stats.store_hits`` counts disk loads, ``stats.store_puts``
    write-backs).

    ``binned=True`` builds device-binned log2 profiles through the
    fused ``kernels/reuse_hist`` path instead of exact histograms —
    faster at scale, hit rates within ~1e-3 of the exact profiles, and
    stored under distinct (builder-fingerprinted) disk keys.

    ``sampled=R`` builds SHARDS-sampled profiles at rate R through
    :mod:`repro.core.reuse.sampled` — constant memory at any trace
    length, each profile carrying its declared ``error_bound`` — also
    under distinct disk keys (``+sampled{R}``), so exact, binned, and
    sampled cells of one workload never collide in a shared store.
    A per-request ``PredictionRequest.sampled_rate`` overrides the
    session rate cell by cell through a cached variant builder.
    """

    def __init__(
        self,
        *,
        profile_builder=None,
        cache_model=None,
        runtime_model=None,
        cache: bool = True,
        window_size: int | None = None,
        binned: bool = False,
        sampled: float | None = None,
        store=None,
        artifact_dir=None,
        verify_fingerprints: bool = False,
    ):
        if profile_builder is None:
            profile_builder = MimicProfileBuilder(
                window_size=window_size, binned=binned, sampled=sampled
            )
        elif binned and not getattr(profile_builder, "binned", False):
            raise ValueError(
                "binned=True only configures the default builder; pass a "
                "builder with binned profile support instead"
            )
        elif (sampled is not None
              and getattr(profile_builder, "sampled", None) != sampled):
            raise ValueError(
                "sampled=R only configures the default builder; pass a "
                "builder with sampled profile support instead"
            )
        self.builder = profile_builder
        self._sampled_builders: dict[float, object] = {}
        self.window_size = window_size
        if isinstance(cache_model, str):
            # shorthand for the analytical backends ("batched"/"numpy")
            cache_model = AnalyticalSDCM(backend=cache_model)
        self.cache_model = cache_model or AnalyticalSDCM()
        self.runtime_model = runtime_model  # None -> per-target default
        self.cache_enabled = cache
        if store is None and artifact_dir is not None:
            from repro.validate.store import ArtifactStore

            store = ArtifactStore(artifact_dir)
        self.store = store
        self.verify_fingerprints = verify_fingerprints
        self.stats = SessionStats()
        self._trace_ids: dict[int, str] = {}       # id(source) -> trace_id
        # pins every cached source: id() keys are only valid while the
        # object is alive, so a recycled address must never hit the map
        self._sources: dict[int, object] = {}
        self._traces: dict[str, LabeledTrace] = {}
        self._rd: dict = {}
        self._privates: dict = {}
        self._shared: dict = {}
        self._profiles: dict = {}

    # --- artifact construction (each key computed exactly once) -----------

    def identify(self, source) -> str:
        """Trace id of a source WITHOUT materializing its trace when a
        declared fingerprint is available.

        Registry-resolved workloads carry ``declared_fingerprint`` — a
        stable key over (name, generator version, resolved kwargs) —
        which becomes the trace id directly, so artifact cells can be
        answered from the store without ever building the trace.
        Undeclared sources fall back to :meth:`load` (materialize +
        content-hash), preserving the old behaviour.
        """
        sid = id(source)
        if self.cache_enabled and sid in self._trace_ids:
            return self._trace_ids[sid]
        fp = getattr(source, "declared_fingerprint", None)
        if fp:
            tid = str(fp)
            if self.cache_enabled:
                self._trace_ids[sid] = tid
                self._sources[sid] = source
            return tid
        tid, _trace = self.load(source)
        return tid

    def load(self, source) -> tuple[str, LabeledTrace]:
        """Coerce + trace + id a source (cached).

        Declared sources are keyed by their declared fingerprint;
        anything else is content-hashed after materialization.  With
        caching disabled both the id and the hash are skipped (nothing
        is keyed on them) — the deprecated shim must not pay O(N)
        hashing the legacy predictor never did.
        """
        sid = id(source)  # the caller's object, not the coercion wrapper
        if self.cache_enabled and sid in self._trace_ids:
            tid = self._trace_ids[sid]
            return tid, self._trace_of(tid, source)
        if not self.cache_enabled:
            trace = _materialize(source)
            self.stats.trace_builds += 1
            return "", trace
        fp = getattr(source, "declared_fingerprint", None)
        if fp:
            tid = str(fp)
            self._trace_ids[sid] = tid
            self._sources[sid] = source
            return tid, self._trace_of(tid, source)
        trace = _materialize(source)
        self.stats.trace_builds += 1
        tid = trace_content_id(trace)
        self._trace_ids[sid] = tid
        self._sources[sid] = source
        self._traces.setdefault(tid, trace)
        return tid, trace

    def _trace_of(self, tid: str, source) -> LabeledTrace:
        """Materialize (or fetch) the trace behind an already-known id.

        This is the ONLY place declared sources build their trace, so
        ``stats.trace_builds`` counts real materializations — the
        warm-store zero-build property is asserted on it.
        """
        if self.cache_enabled and tid in self._traces:
            return self._traces[tid]
        trace = _materialize(source)
        self.stats.trace_builds += 1
        if self.cache_enabled:
            self._traces[tid] = trace
        if getattr(source, "declared_fingerprint", None):
            self._check_declared(tid, source, trace)
        return trace

    def _check_declared(self, tid: str, source, trace: LabeledTrace) -> None:
        """Record (and optionally verify) the content hash behind a
        declared fingerprint.

        First materialization writes ``trace_content_id`` into the
        store's workload meta; under ``verify_fingerprints=True`` a
        later materialization that hashes differently — a generator
        whose declared version lied — raises instead of silently
        serving stale artifacts.
        """
        if self.store is None:
            return
        meta = dict(self.store.get_json("workload", tid) or {})
        recorded = meta.get("trace_content_id")
        if recorded is None:
            meta.update(
                trace_content_id=trace_content_id(trace),
                refs=len(trace),
                workload=getattr(source, "workload_name", None)
                or meta.get("workload"),
            )
            self.store.put_json("workload", tid, meta)
        elif self.verify_fingerprints:
            cid = trace_content_id(trace)
            if cid != recorded:
                raise RuntimeError(
                    f"declared fingerprint {tid} of "
                    f"{getattr(source, 'workload_name', source)!r} is stale: "
                    f"trace content hash {cid} != recorded {recorded} — "
                    "bump the generator version"
                )

    def _reuse_distances(self, tid: str, trace: LabeledTrace, line: int):
        key = (tid, line)
        if self.cache_enabled and key in self._rd:
            return self._rd[key]
        from repro.core.reuse.distance import reuse_distances

        self.stats.rd_builds += 1
        rd = reuse_distances(trace.addresses, line)
        if self.cache_enabled:
            self._rd[key] = rd
        return rd

    def _private_traces(self, tid: str, trace: LabeledTrace, cores: int):
        if cores == 1:
            return [trace]
        key = (tid, cores)
        if self.cache_enabled and key in self._privates:
            return self._privates[key]
        self.stats.mimic_builds += 1
        privs = self.builder.private_traces(trace, cores)
        if self.cache_enabled:
            self._privates[key] = privs
        return privs

    def _shared_trace(self, tid: str, privs, cores: int, strategy: str,
                      seed: int):
        key = (tid, cores, strategy, seed)
        if self.cache_enabled and key in self._shared:
            return self._shared[key]
        self.stats.interleave_builds += 1
        shared = self.builder.interleave(privs, strategy, seed)
        if self.cache_enabled:
            self._shared[key] = shared
        return shared

    def _resolve_window(self, window_size: int | None) -> int | None:
        """Explicit override > session default > builder default."""
        if window_size is not None:
            return window_size or None  # 0 forces the in-memory path
        if self.window_size is not None:
            return self.window_size or None  # normalized: one cache key
        return getattr(self.builder, "window_size", None)

    def _builder_for(self, sampled: float | None):
        """The Session builder, or a cached sampled-rate variant when a
        per-request rate overrides it (``PredictionRequest.sampled_rate``).
        Variants share nothing but the store — their fingerprints embed
        the rate, so store keys never collide across rates."""
        if sampled is None:
            return self.builder
        rate = float(sampled)
        if getattr(self.builder, "sampled", None) == rate:
            return self.builder
        if not hasattr(self.builder, "with_sampled"):
            raise ValueError(
                "per-request sampled_rate needs a profile builder with "
                "with_sampled support (the default MimicProfileBuilder)"
            )
        variant = self._sampled_builders.get(rate)
        if variant is None:
            variant = self.builder.with_sampled(rate)
            self._sampled_builders[rate] = variant
        return variant

    def artifacts(self, source, cores: int, *, strategy: str = "round_robin",
                  seed: int = 0, line_size: int = 64,
                  window_size: int | None = None,
                  sampled: float | None = None,
                  need_traces: bool = False) -> ProfileArtifacts:
        """PRD/CRD profiles (+ underlying traces) for one grid cell.

        ``window_size`` (or the Session/builder default) routes the
        reuse-distance passes through the streaming layer: bit-identical
        profiles, peak scan memory bounded by the window + working set,
        and the interleaved shared trace never materialized (for the
        deterministic strategies) — ``artifacts.shared`` is ``None``.

        ``sampled`` overrides the builder's sampling rate for this cell
        (``None`` keeps the builder mode — exact unless the Session was
        built with ``sampled=R``); the cell caches and store keys embed
        the effective rate, so exact and sampled artifacts coexist.

        ``need_traces`` guarantees the returned artifact carries the
        mimicked private/shared traces: profile cells served from the
        disk store arrive trace-less (only the histograms persist) and
        are rematerialized through the stage caches for trace-consuming
        models (ExactLRU ground truth).
        """
        with telemetry.span("session.artifacts"):
            ws = self._resolve_window(window_size)
            builder = self._builder_for(sampled)
            rate = getattr(builder, "sampled", None)
            if self.cache_enabled:
                # id only — the trace is materialized lazily, so cells
                # served from memory/disk never build it (store hits cost
                # zero trace builds)
                tid = self.identify(source)
                trace = None
            else:
                tid, trace = self.load(source)
            key = (tid, line_size, cores, strategy, seed, ws, rate)
            if self.cache_enabled and key in self._profiles:
                self.stats.profile_hits += 1
                art = self._profiles[key]
                if need_traces and not art.privates:
                    art = self._materialize_traces(
                        art, self._trace_of(tid, source)
                    )
                    self._profiles[key] = art
                return art
            if self.cache_enabled and self.store is not None:
                from repro.validate.store import (
                    builder_fingerprint,
                    load_profile_artifacts,
                )

                art = load_profile_artifacts(
                    self.store, tid, line_size, cores, strategy, seed, ws,
                    builder_fingerprint(builder),
                )
                if art is not None:
                    self.stats.store_hits += 1
                    if need_traces:
                        art = self._materialize_traces(
                            art, self._trace_of(tid, source)
                        )
                    self._profiles[key] = art
                    return art
            if trace is None:
                trace = self._trace_of(tid, source)
            binned = bool(getattr(builder, "binned", False))
            if ws:
                art = self._streaming_artifacts(
                    tid, trace, cores, strategy, seed, line_size, ws, builder
                )
            elif cores == 1:
                if rate is not None:
                    # sampled cells bypass the exact-rd cache entirely: the
                    # builder hash-filters the trace itself
                    prof = builder.profile(trace, line_size)
                else:
                    rds = self._reuse_distances(tid, trace, line_size)
                    if hasattr(builder, "profile_of_distances"):
                        prof = builder.profile_of_distances(rds)
                    else:
                        prof = profile_from_distances(rds)
                art = ProfileArtifacts(
                    trace_id=tid, cores=1, strategy=strategy, seed=seed,
                    line_size=line_size, privates=[trace], shared=trace,
                    prd=prof, crd=prof, binned=binned, sampled=rate,
                )
            else:
                privs = self._private_traces(tid, trace, cores)
                shared = self._shared_trace(tid, privs, cores, strategy, seed)
                # PRD of the master core (cores are symmetric by construction)
                prd = builder.profile(privs[0], line_size)
                crd = builder.profile(shared, line_size)
                art = ProfileArtifacts(
                    trace_id=tid, cores=cores, strategy=strategy, seed=seed,
                    line_size=line_size, privates=privs, shared=shared,
                    prd=prd, crd=crd, binned=binned, sampled=rate,
                )
            self.stats.profile_builds += 1
            if self.cache_enabled:
                self._profiles[key] = art
                if self.store is not None:
                    from repro.validate.store import (
                        builder_fingerprint,
                        save_profile_artifacts,
                    )

                    save_profile_artifacts(
                        self.store, art, builder_fingerprint(builder)
                    )
                    self.stats.store_puts += 1
            return art

    def _materialize_traces(self, art: ProfileArtifacts,
                            trace: LabeledTrace) -> ProfileArtifacts:
        """Re-attach mimicked traces to a store-loaded (trace-less)
        profile cell.  Mimicry/interleaving are cheap O(N) rebuilds and
        go through the stage caches; the expensive profile passes are
        NOT rerun.  Streaming cells keep ``shared=None`` (the
        interleaved trace is never materialized on that path)."""
        if art.cores == 1:
            return dataclasses.replace(art, privates=[trace], shared=trace)
        privs = self._private_traces(art.trace_id, trace, art.cores)
        shared = art.shared
        if shared is None and not art.window_size:
            shared = self._shared_trace(
                art.trace_id, privs, art.cores, art.strategy, art.seed
            )
        return dataclasses.replace(art, privates=privs, shared=shared)

    def _streaming_artifacts(self, tid, trace, cores, strategy, seed,
                             line_size, ws, builder=None) -> ProfileArtifacts:
        """Window-bounded cell build (ISSUE-2 tentpole).

        Uses the builder's streaming hooks when present (the default
        ``MimicProfileBuilder`` provides them); a custom builder without
        them falls back to its own in-memory stages.
        """
        self.stats.streaming_builds += 1
        builder = builder if builder is not None else self.builder
        binned = bool(getattr(builder, "binned", False))
        rate = getattr(builder, "sampled", None)
        if hasattr(builder, "profile_windows"):
            def stream_profile(t, line):
                return builder.profile_windows(t, line, ws)
        else:  # custom builder without streaming hooks: its own stages
            def stream_profile(t, line):
                return builder.profile(t, line)
        if cores == 1:
            prof = stream_profile(trace, line_size)
            return ProfileArtifacts(
                trace_id=tid, cores=1, strategy=strategy, seed=seed,
                line_size=line_size, privates=[trace], shared=trace,
                prd=prof, crd=prof, window_size=ws, binned=binned,
                sampled=rate,
            )
        privs = self._private_traces(tid, trace, cores)
        prd = stream_profile(privs[0], line_size)
        if (
            strategy in ("round_robin", "chunked")
            and hasattr(builder, "shared_profile")
        ):
            crd, shared = builder.shared_profile(
                privs, strategy, seed, line_size, ws
            )
        else:
            # uniform (or a builder without streaming hooks) needs the
            # materialized interleave: go through the Session cache so
            # it is built once across line sizes/targets
            shared = self._shared_trace(tid, privs, cores, strategy, seed)
            crd = stream_profile(shared, line_size)
        return ProfileArtifacts(
            trace_id=tid, cores=cores, strategy=strategy, seed=seed,
            line_size=line_size, privates=privs, shared=shared,
            prd=prd, crd=crd, window_size=ws, binned=binned,
            sampled=rate,
        )

    # --- execution --------------------------------------------------------

    def predict(self, source, request: PredictionRequest) -> PredictionSet:
        """Execute the full grid; hit rates evaluated in one batched
        call when the cache model supports grids."""
        return self.predict_many([(source, request)])[0]

    def predict_many(
        self, items: list[tuple[object, PredictionRequest]]
    ) -> list[PredictionSet]:
        """Execute many independent (source, request) pairs with ONE
        cache-model grid evaluation across all of them.

        This is the coalescible surface the prediction service batches
        through (:mod:`repro.service`): every grid cell of every request
        is gathered (profiles served from the Session caches / disk
        store as usual) and the whole union goes to
        ``cache_model.hit_rates_grid`` — with the batched SDCM backend
        that is a single vmapped, jitted kernel call for N requests
        instead of N per-request loops.  Results are fanned back out in
        input order, bit-identical to ``[predict(s, r) for s, r in
        items]``.
        """
        with telemetry.span("session.predict") as span:
            need_traces = bool(
                getattr(self.cache_model, "needs_traces", False))
            plans = []
            flat: list[tuple[object, ProfileArtifacts]] = []
            for source, request in items:
                tid = self.identify(source)
                cells = list(request.cells())
                if not cells:
                    raise ValueError(
                        f"request matched no grid cells: {request.describe()}"
                    )
                arts = [
                    self.artifacts(
                        source, cell.cores, strategy=cell.strategy,
                        seed=request.seed,
                        line_size=cell.target.levels[0].line_size,
                        window_size=request.window_size,
                        sampled=request.sampled_rate,
                        need_traces=need_traces,
                    )
                    for cell in cells
                ]
                plans.append((tid, request, cells, arts))
                flat.extend(
                    (cell.target, art) for cell, art in zip(cells, arts))
            span.count(len(flat))

            from repro.api import batched

            compiled_before = batched.compile_count()
            if hasattr(self.cache_model, "hit_rates_grid"):
                rate_dicts = self.cache_model.hit_rates_grid(flat)
            else:
                rate_dicts = [
                    self.cache_model.hit_rates(t, a) for t, a in flat
                ]
            self.stats.kernel_compiles += (
                batched.compile_count() - compiled_before
            )

            out: list[PredictionSet] = []
            offset = 0
            for tid, request, cells, arts in plans:
                rates_slice = rate_dicts[offset:offset + len(cells)]
                offset += len(cells)
                out.append(
                    self._assemble(tid, request, cells, arts, rates_slice)
                )
            return out

    def _assemble(self, tid, request, cells, arts, rate_dicts
                  ) -> PredictionSet:
        predictions = []
        with telemetry.span("runtime.model", n=len(cells)):
            for cell, art, rates in zip(cells, arts, rate_dicts):
                timing = {}
                rt = None
                if request.counts is not None:
                    # precedence: per-request named model > the Session's
                    # injected stage > the target's default
                    if request.runtime_model is not None:
                        rt = resolve_runtime_model(
                            request.runtime_model, cell.target
                        )
                    else:
                        rt = self.runtime_model or default_runtime_model(
                            cell.target
                        )
                    timing = rt.runtime(
                        cell.target, rates, request.counts, cell.cores,
                        mode=cell.mode, gap_bytes=request.gap_bytes,
                    )
                predictions.append(
                    CellPrediction(
                        target=cell.target.name,
                        cores=cell.cores,
                        strategy=cell.strategy,
                        mode=cell.mode,
                        hit_rates=rates,
                        t_pred_s=timing.get("t_pred_s"),
                        t_mem_s=timing.get("t_mem_s"),
                        t_cpu_s=timing.get("t_cpu_s"),
                        runtime_model=(getattr(rt, "name", None)
                                       if rt else None),
                        private_profile=(art.prd if request.keep_profiles
                                         else None),
                        shared_profile=(art.crd if request.keep_profiles
                                        else None),
                    )
                )
        return PredictionSet(
            predictions,
            cache_model=getattr(self.cache_model, "name", "custom"),
            trace_id=tid,
        )

    # --- single-cell conveniences ----------------------------------------

    def hit_rates(self, source, target, cores: int, *,
                  strategy: str = "round_robin", seed: int = 0
                  ) -> dict[str, float]:
        target = resolve_target(target)
        art = self.artifacts(
            source, cores, strategy=strategy, seed=seed,
            line_size=target.levels[0].line_size,
        )
        return self.cache_model.hit_rates(target, art)

    def ground_truth_hit_rates(self, source, target, cores: int, *,
                               strategy: str = "round_robin", seed: int = 0
                               ) -> dict[str, float]:
        """Exact-LRU simulation through the same stage interface.

        ExactLRU simulates the materialized traces, so this always
        builds in-memory artifacts (``window_size=0``) — it works on a
        streaming Session too, cached under the in-memory key.
        """
        target = resolve_target(target)
        art = self.artifacts(
            source, cores, strategy=strategy, seed=seed,
            line_size=target.levels[0].line_size, window_size=0,
            need_traces=True,
        )
        return ExactLRU().hit_rates(target, art)
