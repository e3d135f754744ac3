"""``benchmark/selfcheck.py`` in tier-1 (ROADMAP D7, PERF.md section 7): the
trace reduction against a recorded trace with known sums, the operation and
byte counters against hand counts, and the index (every cell resolves to
files that exist, every per-layer metric has a reader and moves an
end-to-end metric of each cell that reports it). Seconds on the CPU, so a
change that breaks the yardstick fails here and not on the chip."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_selfcheck_passes():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "selfcheck.py")],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[-1] == "selfcheck passed"
    assert all(ln.startswith("ok  ") for ln in lines[:-1])
    # the trace reduction, the counters and the index were all looked at
    for part in ("trace:", "counts:", "peaks:", "index:"):
        assert any(ln.startswith("ok   " + part) for ln in lines), part
    # every per-layer metric of BENCHMARK.json has its reader
    assert any("train_host_ms has a reader" in ln for ln in lines)
