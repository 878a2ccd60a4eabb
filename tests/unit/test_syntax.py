"""Fast static-analysis gate for the whole tree: syntax + tpu-lint.

A SyntaxError in a module that tests import (docs/build.py had one — an
f-string expression containing a backslash, illegal before Python 3.12) breaks
pytest COLLECTION of the importing test file: the suite reports a collection
error and silently stops running every test in that file. This gate compiles
every source file directly, so a syntax regression fails THIS test loudly with
the offending file and line instead.

The second gate runs tpu-lint (:mod:`unionml_tpu.analysis`) over the package:
the tree must stay clean — real findings get fixed, justified exceptions carry
an inline ``# tpu-lint: disable=RULE`` with a why-comment — so the analyzer is
a permanent CI gate, not a demo. The incremental run must stay several times
cheaper than the cold one, which keeps the gate inside the tier-1 envelope.

Equivalent CLI gates (usable as pre-commit / CI steps on their own):
``python -m compileall -q unionml_tpu docs tests`` and
``unionml-tpu lint unionml_tpu``.
"""

import compileall
import re
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

#: trees whose .py files must all parse; benchmarks and templates included —
#: templates are exec'd by the framework-app tests, benchmarks by operators
_TREES = ("unionml_tpu", "docs", "tests", "benchmarks")


def test_every_source_file_compiles():
    failures = []
    for tree in _TREES:
        root = REPO / tree
        if not root.exists():
            continue
        # quiet=1 still prints per-file errors to stdout (pytest captures and
        # shows them on failure); rx excludes nothing — the whole tree gates
        ok = compileall.compile_dir(
            str(root), quiet=1, force=False, rx=re.compile(r"/\.git/")
        )
        if not ok:
            failures.append(tree)
    assert not failures, (
        f"syntax errors under {failures}; run `python -m compileall -q "
        + " ".join(_TREES)
        + "` for details"
    )


def test_tree_is_lint_clean():
    """The package passes tpu-lint with zero active findings (fixed, or
    suppressed inline with a justification) — and the incremental run is
    several times cheaper than the cold one."""
    from unionml_tpu.analysis import clear_index_cache, render_text, run_lint

    clear_index_cache()  # measure the true cold path even if an earlier test linted
    start = time.perf_counter()
    result = run_lint([REPO / "unionml_tpu"])
    elapsed = time.perf_counter() - start
    assert result.clean, "tpu-lint findings (fix, or suppress with justification):\n" + render_text(result)
    assert result.files > 50, "lint walked suspiciously few files — path wiring broke"
    # incremental contract: the content-hash index cache makes a warm run
    # skip parsing and per-file re-checks entirely — this is what keeps the
    # gate cheap as the tree grows. Held as a ratio of the two runs, taken in
    # this process under the same load (a cold pass of ~110 files reads 5-6 s
    # alone and twice that beside five other test workers, the warm one a
    # sixteenth of it): a wall-clock budget would fail on the machine's load,
    # not on the tree
    start = time.perf_counter()
    warm = run_lint([REPO / "unionml_tpu"])
    warm_elapsed = time.perf_counter() - start
    assert warm.clean
    assert warm.index_stats["misses"] == 0, "warm run rebuilt summaries — cache invalidation broke"
    assert warm_elapsed * 3 < elapsed, (
        f"warm (incremental) lint took {warm_elapsed:.2f}s against {elapsed:.2f}s cold: "
        "not several times cheaper"
    )


def test_lint_gate_fails_on_seeded_violation(tmp_path):
    """The gate actually gates: a seeded violation exits non-zero through the
    same entry points the CI/CLI use."""
    from unionml_tpu.analysis import run_lint
    from unionml_tpu.analysis.engine import main as lint_main

    seeded = tmp_path / "seeded.py"
    seeded.write_text("import os\nWORKERS = int(os.environ['WORKERS'])\n")
    assert not run_lint([seeded]).clean
    assert lint_main([str(seeded)]) == 1


def test_lint_gate_fails_on_seeded_lock_cycle(tmp_path):
    """The whole-program side of the gate gates too: an actual two-lock cycle
    seeded across two modules must fail through the same entry points — this
    is the deadlock class the per-file rules structurally cannot see."""
    from unionml_tpu.analysis import run_lint
    from unionml_tpu.analysis.engine import main as lint_main

    pkg = tmp_path / "seededpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "fleet.py").write_text(
        "import threading\n"
        "from seededpkg.engine import Engine\n\n\n"
        "class Fleet:\n"
        "    def __init__(self):\n"
        "        self._scale_lock = threading.Lock()\n"
        "        self._engine = Engine()\n\n"
        "    def scale(self):\n"
        "        with self._scale_lock:\n"
        "            self._engine.drain(self)\n"
    )
    (pkg / "engine.py").write_text(
        "import threading\n"
        "import seededpkg.fleet\n\n\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n\n"
        "    def drain(self, fleet: seededpkg.fleet.Fleet):\n"
        "        with self._lock:\n"
        "            fleet.scale()\n"
    )
    result = run_lint([pkg])
    assert not result.clean
    assert [finding.rule for finding in result.findings] == ["TPU010"]
    assert "lock-order cycle" in result.findings[0].message
    assert lint_main([str(pkg)]) == 1


def test_lint_gate_fails_on_seeded_flow_violations(tmp_path):
    """The exception-path flow rules gate too: one seeded fixture per rule
    (TPU016 leak-on-exception, TPU017 charge-without-refund, TPU018
    lock-held-across-yield, TPU019 unreleased-on-early-return) must fail
    through the same entry points the CI/CLI use — these are the classes the
    syntactic rules structurally cannot see without a CFG."""
    from unionml_tpu.analysis import run_lint
    from unionml_tpu.analysis.engine import main as lint_main

    pkg = tmp_path / "flowpkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "leak.py").write_text(  # TPU016: request() raises -> conn leaks
        "from http.client import HTTPConnection\n\n\n"
        "def fetch(host, payload):\n"
        "    conn = HTTPConnection(host)\n"
        '    conn.request("POST", "/step", payload)\n'
        "    body = conn.getresponse().read()\n"
        "    conn.close()\n"
        "    return body\n"
    )
    (pkg / "charge.py").write_text(  # TPU017: charged, then an unguarded raise path
        "def submit(registry, tenant, grammar, compile_grammar):\n"
        "    retry_after = registry.try_admit(tenant)\n"
        "    if retry_after is not None:\n"
        '        raise RuntimeError("throttled")\n'
        "    compile_grammar(grammar)\n"
        "    return True\n"
    )
    (pkg / "stream.py").write_text(  # TPU018: consumer stalls -> lock held forever
        "import threading\n\n\n"
        "class Streamer:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n\n"
        "    def stream(self, chunks):\n"
        "        with self._lock:\n"
        "            for chunk in chunks:\n"
        "                yield chunk\n"
    )
    (pkg / "early.py").write_text(  # TPU019: early return skips the close
        "def read_config(path, strict):\n"
        "    handle = open(path)\n"
        "    if strict:\n"
        "        return None\n"
        "    handle.close()\n"
        "    return True\n"
    )
    result = run_lint([pkg])
    assert not result.clean
    seeded = {finding.rule for finding in result.findings}
    assert {"TPU016", "TPU017", "TPU018", "TPU019"} <= seeded
    assert lint_main([str(pkg)]) == 1
