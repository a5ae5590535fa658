#!/usr/bin/env bash
# CI entry point.
#
#   tools/ci.sh            tier-1 lane: import hygiene, fast tests
#                          (-m "not slow"), docs check, subset-cache
#                          smoke benchmark
#   tools/ci.sh --tests    tier-1 tests only        (matrix job: tests)
#   tools/ci.sh --hygiene  hygiene + smoke bench    (matrix job: hygiene)
#   tools/ci.sh --docs     docs lane: intra-repo link check (anchors
#                          included) and every committed
#                          benchmarks/results/*.json baseline must be
#                          referenced from README.md or docs/
#                          (matrix job: docs)
#   tools/ci.sh --full     everything: slow driver/serving tests + the
#                          benchmark regression gates (tools/check_bench.py
#                          compares fresh subset_cache/lattice/serving/
#                          train_driver/scenarios/serving_mp/
#                          serving_socket/serving_scenarios/roofline/
#                          frontier numbers
#                          against the committed benchmarks/results/*.json
#                          baselines; REPRO_BENCH_TOLERANCE overrides the
#                          30% gate on noisy runners)
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

FULL=0 TESTS=1 HYGIENE=1 DOCS=1
case "${1:-}" in
    --full)    FULL=1 ;;
    --tests)   HYGIENE=0 DOCS=0 ;;
    --hygiene) TESTS=0 DOCS=0 ;;
    --docs)    TESTS=0 HYGIENE=0 ;;
    "") ;;
    *) echo "usage: tools/ci.sh [--full|--tests|--hygiene|--docs]" >&2
       exit 2 ;;
esac

if [[ "$HYGIENE" == 1 ]]; then
echo "== optional-dependency import hygiene =="
# hypothesis (property tests) and jax (accelerator extras) are optional
# on minimal containers: any test importing them without a preceding
# pytest.importorskip guard breaks collection there.
python - <<'PY'
import pathlib
import re
import sys

bad = []
for mod in ("hypothesis", "jax"):
    for path in pathlib.Path("tests").glob("*.py"):
        src = path.read_text()
        imp = re.search(rf"^\s*(?:from|import)\s+{mod}\b", src, re.M)
        if imp is None:
            continue
        # the guard must RUN BEFORE the first import executes
        skip = re.search(rf"importorskip\(\s*['\"]{mod}['\"]\s*\)", src)
        if skip is None or skip.start() > imp.start():
            bad.append(f"{path} ({mod})")


def guarded_suite(pattern, why, *, require_slow_when=None):
    """Suites that import repro.* (pulling jax transitively) and may run
    drivers or spawn worker processes: every file must guard jax
    explicitly, and files matching ``require_slow_when`` must mark
    themselves slow so the tier-1 lane stays fast.  Offenders are listed
    by name so the failure is actionable, and a missing suite is itself
    an offense (the gate must not pass vacuously)."""
    files = sorted(pathlib.Path("tests").glob(pattern))
    if not files:
        bad.append(f"tests/{pattern} (missing: {why})")
    for path in files:
        src = path.read_text()
        if 'importorskip("jax")' not in src and \
                "importorskip('jax')" not in src:
            bad.append(f"{path} (no jax importorskip)")
        if require_slow_when is None or require_slow_when(src):
            if "pytest.mark.slow" not in src:
                bad.append(f"{path} (no slow marker: {why})")


guarded_suite("test_scenarios*.py", "scenario suite",
              require_slow_when=lambda src: "run_online" in src)
# the lattice parity suite property-tests all 2^N - 1 subsets per draw
# and spins up process shards for the wire-contract case: jax must be
# guarded and the process-backend cases slow-marked
guarded_suite("test_lattice_eval*.py", "lattice parity suite")
# multi-process serving suites spawn worker processes (seconds each on
# the spawn context): slow-marked wholesale, nightly --full runs them
guarded_suite("test_serving_mp*.py", "process-shard serving suite")
# socket suites additionally spawn TCP shard-host processes and an HTTP
# front door: slow-marked wholesale like the mp suites
guarded_suite("test_serving_socket*.py", "socket-shard serving suite")
guarded_suite("test_serving_scenarios*.py", "scenario serving suite")
# device-resident training: the parity suite trains full drivers for
# the bit-identical device-vs-host assertions (slow when it does), and
# the roofline suite compiles/times jitted programs
guarded_suite("test_device_replay*.py", "device replay parity suite",
              require_slow_when=lambda src: "run_off_policy" in src)
guarded_suite("test_roofline*.py", "roofline measurement suite")
# selector policies (cascade/MCT/hybrid) spin serving planes and score
# scenario segments; anything training RL arms online must be slow
guarded_suite("test_selection*.py", "selector policy suite",
              require_slow_when=lambda src: "run_online" in src)
# observability: the unit suite stays fast; anything driving online
# training or the process-shard backend must be slow-marked
guarded_suite("test_obs*.py", "observability suite",
              require_slow_when=lambda src: "run_online" in src
              or 'transport="process"' in src)
if bad:
    sys.exit("optional dependency imported without a preceding "
             "pytest.importorskip guard (or serving/scenario test "
             "hygiene violation): " + ", ".join(bad))
print("ok")
PY
fi

if [[ "$DOCS" == 1 || "$FULL" == 1 ]]; then
echo "== docs: intra-repo links + baseline coverage =="
python - <<'PY'
import functools
import pathlib
import re
import sys

root = pathlib.Path(".")
pages = [root / "README.md", *sorted((root / "docs").glob("*.md"))]
bad = []


def slug(heading):
    """GitHub's heading -> anchor rule: lowercase, drop punctuation,
    spaces to hyphens."""
    heading = re.sub(r"[^\w\- ]", "", heading.strip().lower())
    return heading.replace(" ", "-")


@functools.lru_cache(maxsize=None)
def anchors_of(path):
    text = pathlib.Path(path).read_text()
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    return {slug(m.group(1))
            for m in re.finditer(r"^#{1,6}\s+(.*)$", text, re.M)}


LINK = re.compile(r"\]\(([^)\s]+)\)")
for page in pages:
    text = re.sub(r"```.*?```", "", page.read_text(), flags=re.S)
    for target in LINK.findall(text):
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        path_part, _, anchor = target.partition("#")
        dest = page if not path_part else \
            (page.parent / path_part).resolve()
        if not dest.exists():
            bad.append(f"{page}: broken link -> {target}")
            continue
        if anchor and dest.suffix == ".md" and \
                anchor not in anchors_of(str(dest)):
            bad.append(f"{page}: broken anchor -> {target}")

# every committed baseline must be documented somewhere a reader looks
corpus = "\n".join(p.read_text() for p in pages)
for res in sorted((root / "benchmarks" / "results").glob("*.json")):
    if res.stem not in corpus:
        bad.append(f"benchmarks/results/{res.name}: baseline not "
                   "referenced in README.md or docs/")

if bad:
    sys.exit("docs check failed:\n  " + "\n  ".join(bad))
print(f"ok ({len(pages)} pages)")
PY
fi

if [[ "$FULL" == 1 ]]; then
    echo "== tests (full, slow included) =="
    python -m pytest -x -q
elif [[ "$TESTS" == 1 ]]; then
    echo "== tier-1 tests =="
    python -m pytest -x -q -m "not slow"
fi

if [[ "$FULL" == 1 ]]; then
    echo "== benchmark regression gates (fresh vs committed baselines) =="
    python tools/check_bench.py subset_cache lattice serving \
        train_driver scenarios serving_mp serving_socket \
        serving_scenarios roofline frontier
elif [[ "$HYGIENE" == 1 ]]; then
    echo "== subset-cache smoke benchmark (50 images) =="
    # scratch results dir: the committed baselines under benchmarks/
    # results/ are the check_bench reference and must not be clobbered
    REPRO_RESULTS_DIR="$(mktemp -d)" REPRO_BENCH_IMAGES=50 \
        python benchmarks/run.py subset_cache
fi

echo "CI OK"
