"""Anti-rot gate for the benchmark harness.

Runs ``python -m benchmarks.run --smoke`` as a subprocess: every benchmark
module must satisfy the harness contract (NAME / PAPER_CLAIM / run) and the
modules with a smoke tier (fig5_sparse_graphs, large_graph_walk, law_sweep,
serve_throughput, fault_sweep) must actually execute at toy sizes.  The large-graph tier must take real walk
steps through EVERY registered engine layout (``repro.core.engine.LAYOUTS``)
plus the compacted bucketed dispatch, so a rotted path — not just the
default one — fails tier 1 here instead of rotting until someone runs the
full suite.  The same smoke run's steps/sec then feed
``benchmarks/check_regression.py`` against the committed baseline in
``results/BENCH_large_graph.json`` — so an order-of-magnitude step-time
regression fails tier 1 too, not just a correctness break.
"""
import json
import os
import subprocess
import sys

from repro.core.engine import LAYOUTS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(cache_dir):
    env = dict(os.environ)
    # benchmarks.run keeps its compile cache where this variable says; a
    # per-test directory keeps the checkout clean
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    return env


def test_benchmarks_smoke_tier_passes(tmp_path):
    json_path = str(tmp_path / "smoke.json")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--smoke", "--json", json_path],
        cwd=REPO,
        env=_env(tmp_path / "jax_cache"),
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert proc.returncode == 0, (
        f"--smoke failed (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    out = proc.stdout
    # the executed smoke tiers must have reported derived metrics
    assert "large_graph_walk[smoke]" in out
    assert "fig5_sparse_graphs[smoke]" in out
    assert "law_sweep[smoke]" in out
    assert "serve_throughput[smoke]" in out
    assert "fault_sweep[smoke]" in out
    assert "FAILED" not in out
    # every registered engine layout + the compacted bucketed dispatch must
    # have taken real walk steps
    for layout in tuple(LAYOUTS) + ("bucketed_compact",):
        assert f"_{layout}_steps_per_sec" in out, (
            f"layout {layout!r} was not exercised by the smoke tier"
        )
    # the --json dump (the regression gate's input) must carry the numbers
    with open(json_path) as f:
        derived = json.load(f)
    assert any(
        k.endswith("_steps_per_sec")
        for k in derived.get("large_graph_walk", {})
    )
    # every chain law must have swept every trap family — the law sweep's
    # presence-gated telemetry keys feed check_regression's missing-key
    # path (labels spelled out here on purpose: shrinking LAWS must break
    # this test, not silently shrink it)
    law_keys = set(derived.get("law_sweep", {}))
    for family in ("ba", "dumbbell", "lollipop"):
        for label in (
            "simple", "uniform", "importance", "mhlj", "heterogeneity",
            "private_g0.1", "private_g1.0",
        ):
            assert f"{family}_{label}_herfindahl" in law_keys, (
                f"law {label!r} vanished from the {family} sweep"
            )
    # every routing law must have served the walk-routed workload — the
    # serving sweep's presence-gated keys (Herfindahl entrapment telemetry,
    # p99 latency, requests/s) feed the same missing-key path
    serve_keys = set(derived.get("serve_throughput", {}))
    for label in (
        "simple", "uniform", "importance", "mhlj", "heterogeneity",
        "private_g0.5",
    ):
        for suffix in ("herfindahl", "p99_ticks", "requests_per_sec"):
            assert f"ba_{label}_{suffix}" in serve_keys, (
                f"routing law {label!r} vanished from the serving sweep "
                f"({suffix})"
            )
    # every fault-sweep leg must have run: the rescue-on AND rescue-off
    # training legs per family plus the trace-replayed serving legs feed
    # check_regression's presence gate ("_rescue"/"_fault_free" suffixes)
    fault_keys = set(derived.get("fault_sweep", {}))
    for fam in ("dumbbell", "ba"):
        assert f"{fam}_excess_fault_free" in fault_keys
        for tag in ("with_rescue", "no_rescue"):
            assert f"{fam}_excess_f5_{tag}" in fault_keys, (
                f"fault leg {tag!r} vanished from the {fam} sweep"
            )
    for suffix in ("p99", "shed_rate"):
        assert f"serve_{suffix}_fault_free" in fault_keys
        assert f"serve_{suffix}_f5_with_rescue" in fault_keys
        assert f"serve_{suffix}_f5_no_rescue" in fault_keys

    # step-time regression gate: fresh smoke numbers vs the committed
    # baseline (generous 2.5x tolerance — catches rot, not noise)
    check = subprocess.run(
        [
            sys.executable,
            os.path.join("benchmarks", "check_regression.py"),
            "--fresh", json_path,
        ],
        cwd=REPO,
        env=_env(tmp_path / "jax_cache"),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert check.returncode == 0, (
        f"check_regression failed (rc={check.returncode})\n"
        f"stdout:\n{check.stdout}\nstderr:\n{check.stderr}"
    )
    assert "no step-time regressions" in check.stdout
