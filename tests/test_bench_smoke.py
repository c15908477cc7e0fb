"""Every benchmark suite runs end-to-end in --smoke mode and emits
schema-valid CSV (DESIGN.md §9).

One subprocess runs ``benchmarks.run --smoke`` (all suites, capped sizes —
numbers are meaningless, wiring is not), then the output is split on the
``# suite=<name>`` section markers and each suite is asserted to have
produced at least one row that parses under the
``repro.perf.schema.parse_csv_row`` contract.  A suite that crashes, goes
silent, or emits a malformed row fails its own parametrized case.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.perf.schema import parse_csv_row, validate_csv

ROOT = Path(__file__).resolve().parents[1]

# Keep in sync with benchmarks/run.py SUITES (asserted below without
# importing the jax-heavy benchmark modules into the test process).
SUITE_NAMES = (
    "sequential",
    "parallel",
    "speedup_full",
    "speedup_half",
    "efficiency_full",
    "efficiency_half",
    "counters",
    "commsteps",
    "moe_dispatch",
    "engine",
    "netsim",
    "verify",
    "sortd",
    "fleet",
    "faults",
    "workloads",
)


@pytest.fixture(scope="session")
def smoke_output() -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [
            sys.executable, "-m", "benchmarks.run", "--smoke",
            "--arrival", "none", "--report", "", "--fleet-report", "",
        ],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, (
        f"benchmarks.run --smoke failed (rc={proc.returncode}):\n"
        f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    )
    return proc.stdout


def _sections(text: str) -> "dict[str, list[str]]":
    """Rows grouped by the preceding ``# suite=<name>`` marker."""
    sections: dict[str, list[str]] = {}
    current = None
    for line in text.splitlines():
        if line.startswith("# suite="):
            current = line.removeprefix("# suite=").strip()
            sections[current] = []
        elif line.strip() and not line.startswith("#"):
            if line.strip() == "name,us_per_call,derived":
                continue
            if current is not None:
                sections[current].append(line)
    return sections


@pytest.mark.slow
def test_run_py_suite_registry_matches(smoke_output):
    assert tuple(_sections(smoke_output)) == SUITE_NAMES


@pytest.mark.slow
@pytest.mark.parametrize("suite", SUITE_NAMES)
def test_suite_emits_schema_valid_rows(smoke_output, suite):
    rows = _sections(smoke_output).get(suite)
    assert rows, f"suite {suite!r} emitted no CSV rows in --smoke mode"
    for row in rows:
        name, us_per_call, _ = parse_csv_row(row)
        assert us_per_call >= 0.0
        # Row names are namespaced paths; they must at least not collide
        # with the marker syntax.
        assert not name.startswith("#")


@pytest.mark.slow
def test_whole_stream_validates(smoke_output):
    assert validate_csv(smoke_output) == []
