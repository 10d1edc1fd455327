"""Paper-validation walkthrough: a slice of the Tables 6-8 matrix.

Runs a handful of workloads through ``repro.validate`` — the same
harness the committed ``docs/validation.md`` report comes from — with
a disk artifact store, then prints the per-architecture errors next to
the paper's claimed figures and proves the incrementality property by
running the slice a second time.

    PYTHONPATH=src python examples/validate_paper.py

The full matrix (all 14 workloads x 3 CPUs x cores {1,2,4,8} x two
interleave strategies) is the CLI:

    PYTHONPATH=src python -m repro.validate --artifact-dir .validation-cache
"""
from repro.validate import MatrixSpec, paper_claim, run_validation

SPEC = MatrixSpec(
    workloads=("atx", "mvt", "grm", "blk"),
    core_counts=(1, 4),
    strategies=("round_robin",),
    sizes="validation",
)
ARTIFACTS = ".cache/validate-example"

print(f"matrix slice: {SPEC.describe()}\n")
summary = run_validation(SPEC, artifact_dir=ARTIFACTS)

print(f"{'architecture':<18} {'hit err %':>10} {'paper':>7} "
      f"{'runtime err %':>14} {'paper':>7}")
for arch, entry in sorted(summary["aggregates"]["per_arch"].items()):
    claim = paper_claim(arch)
    print(f"{arch:<18} {entry['hit_rate_err_pct']['ours']:>10.2f} "
          f"{claim.hit_rate_err_pct:>7.2f} "
          f"{entry['runtime_err_pct']['ours']:>14.2f} "
          f"{claim.runtime_err_pct:>7.2f}")
agg = summary["aggregates"]["overall"]
print(f"{'overall':<18} {agg['hit_rate_err_pct']['ours']:>10.2f} "
      f"{agg['hit_rate_err_pct']['paper']:>7.2f} "
      f"{agg['runtime_err_pct']['ours']:>14.2f} "
      f"{agg['runtime_err_pct']['paper']:>7.2f}")

stats = summary["session_stats"]
print(f"\nrun 1: {stats['profile_builds']} profile builds, "
      f"{stats['store_hits']} disk-store hits")

# Incrementality: the store makes the second run free of profile work.
again = run_validation(SPEC, artifact_dir=ARTIFACTS)
s2 = again["session_stats"]
print(f"run 2: {s2['profile_builds']} profile builds, "
      f"{s2['store_hits']} disk-store hits  "
      f"(zero reuse-profile recomputations)")
assert s2["profile_builds"] == 0 and s2["rd_builds"] == 0
