"""tpu-lint unit ring: every rule has a must-flag and a near-miss-must-not-flag
fixture, plus suppression-comment, reporter round-trip, CLI, and env-hardening
regression coverage. The companion repo-wide gate (the tree itself must be
lint-clean, under a time budget) lives in test_syntax.py next to the
``compileall`` gate it extends.
"""

import json
import textwrap
from pathlib import Path

import pytest
from click.testing import CliRunner

from unionml_tpu.analysis import render_json, render_text, run_lint
from unionml_tpu.analysis.engine import main as lint_main

REPO = Path(__file__).resolve().parents[2]


def lint_source(tmp_path, source, **kwargs):
    snippet = tmp_path / "snippet.py"
    snippet.write_text(textwrap.dedent(source))
    return run_lint([snippet], **kwargs)


def rule_ids(result):
    return [finding.rule for finding in result.findings]


# --------------------------------------------------------------------- TPU001


def test_tpu001_flags_host_sync_in_jitted_function(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            print("debugging", x)
            return float(x) + 1.0
        """,
    )
    assert rule_ids(result) == ["TPU001", "TPU001"]
    assert "print()" in result.findings[0].message
    assert "float()" in result.findings[1].message


def test_tpu001_follows_intra_module_call_graph(tmp_path):
    # the sync hides one call away from the jitted entry point — and the same
    # helper NOT reachable from any jit is left alone
    result = lint_source(
        tmp_path,
        """
        import numpy as np
        import jax

        def helper(y):
            return np.asarray(y)

        @jax.jit
        def entry(y):
            return helper(y)
        """,
    )
    assert rule_ids(result) == ["TPU001"]
    assert "np.asarray" in result.findings[0].message


def test_tpu001_near_miss_unjitted_and_static_shape(tmp_path):
    # host syncs OUTSIDE jit are normal host code; int() on .shape is static
    # under jit and must not flag
    result = lint_source(
        tmp_path,
        """
        import numpy as np
        import jax

        def host_side(y):
            print("fine here")
            return np.asarray(y)

        @jax.jit
        def entry(y):
            width = int(y.shape[0])
            return y * width
        """,
    )
    assert result.findings == []


def test_tpu001_flags_module_level_block_until_ready(tmp_path):
    # both spellings of the fence: the method form x.block_until_ready() was
    # always flagged; the module-level jax.block_until_ready(x) form is the
    # same sync and must flag too
    result = lint_source(
        tmp_path,
        """
        import jax

        @jax.jit
        def step(x):
            jax.block_until_ready(x)
            return x + 1
        """,
    )
    assert rule_ids(result) == ["TPU001"]
    assert "jax.block_until_ready" in result.findings[0].message


def test_tpu001_near_miss_non_jax_block_until_ready(tmp_path):
    # a same-named helper from ANOTHER module is not jax's fence — only the
    # dotted jax.block_until_ready form (and the zero-arg method) sync; and
    # jax.block_until_ready OUTSIDE jit is ordinary host code
    result = lint_source(
        tmp_path,
        """
        import jax
        import myfence

        @jax.jit
        def step(x):
            myfence.block_until_ready(x)  # someone else's API, takes an arg
            return x + 1

        def host_side(x):
            return jax.block_until_ready(x)
        """,
    )
    assert result.findings == []


def test_tpu001_jit_wrapped_method(tmp_path):
    # the engine idiom: self._fn = jax.jit(self._impl) marks the method jitted
    result = lint_source(
        tmp_path,
        """
        import jax

        class Engine:
            def __init__(self):
                self._fn = jax.jit(self._impl)

            def _impl(self, x):
                return x.item()
        """,
    )
    assert rule_ids(result) == ["TPU001"]
    assert ".item()" in result.findings[0].message


# --------------------------------------------------------------------- TPU002


def test_tpu002_flags_use_after_donate(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import jax

        def train(state, batches, step_fn):
            compiled = jax.jit(step_fn, donate_argnums=0)
            for batch in batches:
                out = compiled(state, batch)
            return state
        """,
    )
    # two findings: the loop back edge carries the donation into the next
    # iteration's `compiled(state, batch)` (a donated buffer passed again),
    # and the donation reaches `return state`
    assert rule_ids(result) == ["TPU002", "TPU002"]
    assert all("'state'" in f.message for f in result.findings)


def test_tpu002_path_sensitive_branches(tmp_path):
    # a load on the branch the donation did NOT take is clean; the line-order
    # heuristic this replaced would have flagged it
    result = lint_source(
        tmp_path,
        """
        import jax

        def step_once(state, batch, step_fn, dry_run):
            compiled = jax.jit(step_fn, donate_argnums=0)
            if dry_run:
                compiled(state, batch)
            else:
                print(state)
            return None
        """,
    )
    assert rule_ids(result) == []


def test_tpu002_near_miss_rebound_and_variable_argnums(tmp_path):
    # rebinding from the result is THE donation idiom; a non-literal
    # donate_argnums (the debug_disable_donation gate) is not analyzable and
    # must not be guessed at
    result = lint_source(
        tmp_path,
        """
        import jax

        def train(state, batches, step_fn, debug_disable_donation=False):
            donate = () if debug_disable_donation else (0,)
            compiled = jax.jit(step_fn, donate_argnums=donate)
            for batch in batches:
                state, metrics = compiled(state, batch)
            return state

        def train_literal(state, batches, step_fn):
            compiled = jax.jit(step_fn, donate_argnums=0)
            for batch in batches:
                state, metrics = compiled(state, batch)
            return state
        """,
    )
    assert result.findings == []


def test_tpu002_attribute_jit_and_decorator(tmp_path):
    result = lint_source(
        tmp_path,
        """
        from functools import partial

        import jax

        @partial(jax.jit, donate_argnums=(0,))
        def update(carry, x):
            return carry + x

        class Engine:
            def __init__(self):
                self._admit = jax.jit(self._admit_impl, donate_argnums=(0,))

            def _admit_impl(self, cache, row):
                return cache

            def good(self, cache, row):
                cache = self._admit(cache, row)
                return cache

            def bad(self, cache, row):
                out = self._admit(cache, row)
                return cache.shape

        def module_level(carry, xs):
            for x in xs:
                carry2 = update(carry, x)
            return carry
        """,
    )
    # Engine.bad's `cache.shape`, plus two in module_level: the loop back
    # edge carries the donation into the next iteration's `update(carry, x)`
    # and the donation reaches `return carry`
    assert rule_ids(result) == ["TPU002", "TPU002", "TPU002"]
    lines = sorted(finding.line for finding in result.findings)
    assert len(lines) == 3


# --------------------------------------------------------------------- TPU003


def test_tpu003_flags_unlocked_mutation(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.total = 0
                self.items = []

            def bump(self):
                self.total += 1
                self.items.append(1)

            def snapshot(self):
                with self._lock:
                    return self.total, list(self.items)
        """,
    )
    assert rule_ids(result) == ["TPU003", "TPU003"]


def test_tpu003_near_miss_locked_init_and_locked_suffix(tmp_path):
    # mutations under the lock, in __init__, or in a *_locked helper (the
    # caller-holds-the-lock convention) are all clean; so is a class with no
    # lock at all
    result = lint_source(
        tmp_path,
        """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Condition()
                self.total = 0

            def bump(self):
                with self._lock:
                    self.total += 1

            def _drain_locked(self):
                self.total = 0

            def snapshot(self):
                with self._lock:
                    return self.total

        class NoLock:
            def __init__(self):
                self.total = 0

            def bump(self):
                self.total += 1
        """,
    )
    assert result.findings == []


def test_tpu003_unguarded_attribute_not_flagged(tmp_path):
    # an attribute NEVER touched under the lock (engine-thread-only state like
    # the decode carry) is outside the discipline and must not flag
    result = lint_source(
        tmp_path,
        """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._carry = None
                self.guarded = 0

            def _decode(self):
                self._carry = (1, 2)

            def stats(self):
                with self._lock:
                    return self.guarded
        """,
    )
    assert result.findings == []


# --------------------------------------------------------------------- TPU004


def test_tpu004_flags_blocking_in_loops_and_async(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import subprocess
        import time

        class Engine:
            def _engine_loop(self):
                while True:
                    time.sleep(0.1)

            async def handle_predict(self, request):
                subprocess.run(["echo", "hi"])
                return request
        """,
    )
    assert rule_ids(result) == ["TPU004", "TPU004"]


def test_tpu004_near_miss_plain_method(tmp_path):
    # a throttle in a plain poller method (not a handler, not a *_loop, not
    # async) is ordinary host code
    result = lint_source(
        tmp_path,
        """
        import time

        class Poller:
            def poll(self):
                time.sleep(0.5)

        def wait_for_backend():
            time.sleep(1.0)
        """,
    )
    assert result.findings == []


# --------------------------------------------------------------------- TPU005


def test_tpu005_flags_bare_env_parse(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import os

        REPLICAS = int(os.environ.get("REPLICAS", "0"))

        def heartbeat():
            raw = os.getenv("HEARTBEAT_S")
            return float(raw)
        """,
    )
    assert rule_ids(result) == ["TPU005", "TPU005"]


def test_tpu005_near_miss_guarded_parse(tmp_path):
    # the hardened pattern: try/except ValueError with a fallback — and
    # int() on non-env values is out of scope entirely
    result = lint_source(
        tmp_path,
        """
        import os

        def replicas():
            try:
                return max(int(os.environ.get("REPLICAS", "0")), 0)
            except ValueError:
                return 0

        def plain(value):
            return int(value)
        """,
    )
    assert result.findings == []


# --------------------------------------------------------------------- TPU006


def test_tpu006_flags_wall_clock_duration_subtraction(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import time

        def measure(step):
            t0 = time.time()
            step()
            return time.time() - t0
        """,
    )
    assert rule_ids(result) == ["TPU006"]


def test_tpu006_flags_wall_clock_deadline_comparison(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import time

        def drain(timeout_s):
            deadline = time.time() + timeout_s
            while time.time() < deadline:
                pass
        """,
    )
    assert rule_ids(result) == ["TPU006"]


def test_tpu006_flags_from_import_time_spelling(tmp_path):
    result = lint_source(
        tmp_path,
        """
        from time import time

        def elapsed(t0=None):
            start = time()
            return time() - start
        """,
    )
    assert rule_ids(result) == ["TPU006"]


def test_tpu006_near_miss_monotonic_and_lone_timestamps(tmp_path):
    # monotonic pairing is the FIX; a lone time.time() timestamp (heartbeat
    # files, deployed_at records) is legitimate wall-clock use; and
    # subtracting a wall-clock value from ANOTHER process (file-read
    # heartbeat) is the one case monotonic cannot serve — none may flag
    result = lint_source(
        tmp_path,
        """
        import time

        def measure(step):
            t0 = time.monotonic()
            step()
            return time.monotonic() - t0

        def heartbeat_record():
            return {"deployed_at": time.time()}

        def heartbeat_age(path):
            return max(0.0, time.time() - float(path.read_text().strip()))
        """,
    )
    assert result.findings == []


def test_tpu006_taint_stays_in_scope(tmp_path):
    # a name tainted in one function must not condemn the same name in
    # another scope where it holds a monotonic value
    result = lint_source(
        tmp_path,
        """
        import time

        def wall():
            t0 = time.time()
            return t0

        def mono():
            t0 = time.monotonic()
            return time.monotonic() - t0
        """,
    )
    assert result.findings == []


# --------------------------------------------------------------------- TPU007


def test_tpu007_flags_unlocked_locked_helper_call(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._free_blocks = []

            def _release_blocks_locked(self, ids):
                self._free_blocks.extend(ids)

            def finish(self, ids):
                self._release_blocks_locked(ids)
        """,
    )
    assert rule_ids(result) == ["TPU007"]
    assert "_release_blocks_locked" in result.findings[0].message


def test_tpu007_near_miss_locked_callers_stay_clean(tmp_path):
    # under the lock, from another *_locked method (the contract propagates),
    # from __init__ (unshared construction), on another object (its lock, not
    # ours), and in a lockless class (naming choice, nothing to hold) — none
    # may flag
    result = lint_source(
        tmp_path,
        """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Condition()
                self._free_blocks = []
                self._seed_locked()

            def _seed_locked(self):
                self._free_blocks.append(0)

            def _drain_locked(self):
                self._seed_locked()

            def finish(self):
                with self._lock:
                    self._seed_locked()

            def proxy(self, other):
                other._seed_locked()

        class Lockless:
            def _helper_locked(self):
                pass

            def run(self):
                self._helper_locked()
        """,
    )
    assert result.findings == []


def test_tpu007_nested_with_and_closures(tmp_path):
    # a call under an OUTER with holding the lock is fine even when the inner
    # with manages something else; a closure's body is its own scope and the
    # call inside it is not charged to the enclosing method
    result = lint_source(
        tmp_path,
        """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()

            def _free_locked(self):
                pass

            def drain(self, path):
                with self._lock:
                    with open(path) as fh:
                        self._free_locked()

            def deferred(self):
                def cb():
                    self._free_locked()
                return cb
        """,
    )
    assert result.findings == []


# --------------------------------------------- suppressions, reporters, CLI


def test_suppression_comment_silences_named_rule(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import os

        A = int(os.environ.get("A", "0"))  # tpu-lint: disable=TPU005
        B = int(os.environ.get("B", "0"))  # tpu-lint: disable=TPU001
        C = int(os.environ.get("C", "0"))  # tpu-lint: disable=all
        """,
    )
    # A and C suppressed; B's comment names the wrong rule so the finding stands
    assert rule_ids(result) == ["TPU005"]
    assert result.findings[0].line == 5
    assert [finding.line for finding in result.suppressed] == [4, 6]
    assert result.exit_code() == 1


def test_suppressed_only_tree_is_clean_exit(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import os

        A = int(os.environ.get("A", "0"))  # tpu-lint: disable=TPU005
        """,
    )
    assert result.clean and result.exit_code() == 0
    assert len(result.suppressed) == 1


def test_json_reporter_round_trip(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import os

        A = int(os.environ.get("A", "0"))
        B = int(os.environ.get("B", "0"))  # tpu-lint: disable=TPU005
        """,
    )
    payload = json.loads(render_json(result))
    assert payload["version"] == 1
    assert payload["counts"] == {"TPU005": 1}
    assert payload["exit_code"] == 1
    assert len(payload["findings"]) == 1 and len(payload["suppressed"]) == 1
    finding = payload["findings"][0]
    assert finding["rule"] == "TPU005" and finding["line"] == 4
    assert finding["path"].endswith("snippet.py")
    # text reporter carries the same location and a summary line
    text = render_text(result, show_suppressed=True)
    assert "snippet.py:4" in text and "[suppressed]" in text
    assert "1 finding(s), 1 suppressed" in text


def test_select_and_ignore(tmp_path):
    source = """
        import os
        import time

        A = int(os.environ.get("A", "0"))

        class Engine:
            def _engine_loop(self):
                time.sleep(1)
    """
    only_env = lint_source(tmp_path, source, select=["TPU005"])
    assert rule_ids(only_env) == ["TPU005"]
    no_env = lint_source(tmp_path, source, ignore=["TPU005"])
    assert rule_ids(no_env) == ["TPU004"]
    with pytest.raises(ValueError, match="unknown rule"):
        lint_source(tmp_path, source, select=["TPU999"])


def test_engine_main_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nA = int(os.environ['A'])\n")
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert lint_main([str(clean)]) == 0
    assert lint_main([str(bad)]) == 1
    capsys.readouterr()
    assert lint_main([str(bad), "--format", "json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["counts"] == {"TPU005": 1}
    assert lint_main([str(tmp_path / "missing.py")]) == 2
    assert lint_main([str(bad), "--select", "NOPE"]) == 2
    syntax_error = tmp_path / "broken.py"
    syntax_error.write_text("def f(:\n")
    assert lint_main([str(syntax_error)]) == 2


def test_cli_lint_command(tmp_path):
    from unionml_tpu.cli import app

    bad = tmp_path / "bad.py"
    bad.write_text("import os\nA = int(os.environ['A'])\n")
    runner = CliRunner()
    result = runner.invoke(app, ["lint", str(bad)])
    assert result.exit_code == 1
    assert "TPU005" in result.output
    result = runner.invoke(app, ["lint", str(bad), "--format", "json"])
    assert result.exit_code == 1
    assert json.loads(result.output)["counts"] == {"TPU005": 1}
    result = runner.invoke(app, ["lint", str(bad), "--ignore", "TPU005"])
    assert result.exit_code == 0


# ------------------------------------------------- env-hardening regression


def test_serve_dp_replicas_tolerates_garbage(monkeypatch, caplog):
    from unionml_tpu._logging import logger
    from unionml_tpu.defaults import SERVE_DP_REPLICAS_ENV_VAR, serve_dp_replicas

    monkeypatch.setattr(logger, "propagate", True)  # let caplog's root handler see records
    monkeypatch.delenv(SERVE_DP_REPLICAS_ENV_VAR, raising=False)
    assert serve_dp_replicas() == 0
    monkeypatch.setenv(SERVE_DP_REPLICAS_ENV_VAR, "3")
    assert serve_dp_replicas() == 3
    monkeypatch.setenv(SERVE_DP_REPLICAS_ENV_VAR, "-2")
    assert serve_dp_replicas() == 0  # clamped, not crashed
    with caplog.at_level("WARNING", logger="unionml_tpu"):
        monkeypatch.setenv(SERVE_DP_REPLICAS_ENV_VAR, "abc")
        assert serve_dp_replicas() == 0
    assert any("abc" in record.message for record in caplog.records)


def test_env_helpers_warn_and_fall_back(monkeypatch, caplog):
    from unionml_tpu._logging import logger
    from unionml_tpu.defaults import env_float, env_int

    monkeypatch.setattr(logger, "propagate", True)  # let caplog's root handler see records
    monkeypatch.setenv("UNIONML_TPU_TEST_KNOB", "not-a-number")
    with caplog.at_level("WARNING", logger="unionml_tpu"):
        assert env_int("UNIONML_TPU_TEST_KNOB", 7) == 7
        assert env_float("UNIONML_TPU_TEST_KNOB", 2.5) == 2.5
    assert sum("not-a-number" in record.message for record in caplog.records) == 2
    monkeypatch.setenv("UNIONML_TPU_TEST_KNOB", "  42 ")
    assert env_int("UNIONML_TPU_TEST_KNOB", 7) == 42
    monkeypatch.setenv("UNIONML_TPU_TEST_KNOB", "0.05")
    assert env_float("UNIONML_TPU_TEST_KNOB", 5.0, minimum=0.1) == 0.1
    monkeypatch.setenv("UNIONML_TPU_TEST_KNOB", "")
    assert env_int("UNIONML_TPU_TEST_KNOB", 7) == 7


# --------------------------------------------------------------------- TPU008


def test_tpu008_flags_unjoined_attribute_thread(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import threading

        class Engine:
            def start(self):
                self._thread = threading.Thread(target=self._loop, daemon=True)
                self._thread.start()

            def close(self):
                self._running = False
        """,
    )
    assert rule_ids(result) == ["TPU008"]
    assert "self._thread" in result.findings[0].message


def test_tpu008_flags_fire_and_forget_and_unjoined_local(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import threading

        class Fleet:
            def kick(self):
                threading.Thread(target=self._loop).start()

            def spawn(self):
                worker = threading.Thread(target=self._loop)
                worker.start()

            def close(self):
                pass
        """,
    )
    assert rule_ids(result) == ["TPU008", "TPU008"]


def test_tpu008_near_misses_stay_clean(tmp_path):
    # joined attribute (the engine idiom), join-through-local-alias (join
    # outside the lock), local joined in-method, container-tracked workers,
    # local promoted to an attribute, a class without close(), and a
    # module-level function — none may flag
    result = lint_source(
        tmp_path,
        """
        import threading

        class Engine:
            def start(self):
                self._thread = threading.Thread(target=self._loop, daemon=True)
                self._thread.start()

            def close(self):
                thread = self._thread
                if thread is not None:
                    thread.join(timeout=10)

        class Warmup:
            def run(self):
                helper = threading.Thread(target=self._probe)
                helper.start()
                helper.join()

            def close(self):
                pass

        class Pool:
            def grow(self):
                worker = threading.Thread(target=self._loop)
                self._workers.append(worker)
                worker.start()

            def promote(self):
                t = threading.Thread(target=self._loop)
                self._scaler = t
                t.start()

            def close(self):
                for worker in self._workers:
                    worker.join()
                self._scaler.join()

        class NoClose:
            def fire(self):
                threading.Thread(target=self._loop).start()

        def module_level():
            threading.Thread(target=print).start()
        """,
    )
    assert rule_ids(result) == []


def test_tpu008_suppression_comment(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import threading

        class Engine:
            def start(self):
                self._thread = threading.Thread(target=self._loop)  # tpu-lint: disable=TPU008

            def close(self):
                pass
        """,
    )
    assert rule_ids(result) == []
    assert [finding.rule for finding in result.suppressed] == ["TPU008"]


# --------------------------------------------------------------------- TPU009


def test_tpu009_flags_request_keyed_dict_without_eviction(tmp_path):
    result = lint_source(
        tmp_path,
        """
        class Registry:
            def __init__(self):
                self._states = {}

            def admit(self, tenant):
                self._states[tenant] = 1
        """,
    )
    assert rule_ids(result) == ["TPU009"]
    assert "self._states" in result.findings[0].message


def test_tpu009_flags_setdefault_and_attribute_keys(tmp_path):
    result = lint_source(
        tmp_path,
        """
        class Recorder:
            def record(self, trace):
                self._inflight.setdefault(trace.request_id, []).append(trace)

        class Census:
            def note(self, session):
                self._counts[session.tenant] = self._counts.get(session.tenant, 0) + 1
        """,
    )
    assert rule_ids(result) == ["TPU009", "TPU009"]


def test_tpu009_near_misses_stay_clean(tmp_path):
    # pop-based eviction, popitem-bounded LRU, del-based pruning, a len()
    # bound check, the filtered-rebuild idiom, server-chosen keys (slot
    # indices), and module-level dicts — none may flag
    result = lint_source(
        tmp_path,
        """
        class PerRequest:
            def start(self, request_id):
                self._inflight[request_id] = 1

            def finish(self, request_id):
                self._inflight.pop(request_id, None)

        class BoundedLRU:
            def note(self, key):
                self._affinity[key] = 1
                while len(self._affinity) > self._capacity:
                    self._affinity.popitem(last=False)

        class Pruned:
            def select(self, tenant):
                self._deficit[tenant] = 0.0
                for tenant in list(self._deficit):
                    del self._deficit[tenant]

        class Rebuilt:
            def note(self, key):
                self._affinity[key] = 1

            def resize(self, n):
                self._affinity = {k: v for k, v in self._affinity.items() if v < n}

        class SlotKeyed:
            def admit(self, slot, session):
                self._sessions[slot] = session

        _MODULE_LEVEL = {}

        def module_insert(tenant):
            _MODULE_LEVEL[tenant] = 1
        """,
    )
    assert rule_ids(result) == []


def test_tpu009_suppression_comment(tmp_path):
    result = lint_source(
        tmp_path,
        """
        class Registry:
            def admit(self, tenant):
                self._states[tenant] = 1  # tpu-lint: disable=TPU009
        """,
    )
    assert rule_ids(result) == []
    assert [finding.rule for finding in result.suppressed] == ["TPU009"]


# ----------------------------------------------- whole-program project rules


def lint_pkg(tmp_path, files, **kwargs):
    """Write a multi-module package fixture and lint the whole tree — the
    cross-module rules only exist at this granularity."""
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    for name, source in files.items():
        (pkg / name).write_text(textwrap.dedent(source))
    return run_lint([pkg], **kwargs)


def test_tpu010_flags_cross_module_lock_cycle(tmp_path):
    # thread 1: Fleet._scale_lock -> Engine._lock; thread 2: Engine._lock ->
    # Fleet._scale_lock (through an annotated callback parameter) — the cycle
    # spans two modules and is invisible to any per-file rule
    result = lint_pkg(
        tmp_path,
        {
            "fleet.py": """
            import threading

            from pkg.engine import Engine


            class Fleet:
                def __init__(self):
                    self._scale_lock = threading.Lock()
                    self._engine = Engine()

                def scale(self):
                    with self._scale_lock:
                        self._engine.drain(self)
            """,
            "engine.py": """
            import threading

            import pkg.fleet


            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()

                def drain(self, fleet: pkg.fleet.Fleet):
                    with self._lock:
                        fleet.scale()
            """,
        },
    )
    assert rule_ids(result) == ["TPU010"]
    message = result.findings[0].message
    assert "lock-order cycle" in message
    assert "[path 1]" in message and "[path 2]" in message
    assert "Fleet._scale_lock" in message and "Engine._lock" in message


def test_tpu010_near_miss_consistent_order_and_reentry(tmp_path):
    # one global order (_scale_lock always before _lock) is the FIX and must
    # not flag; re-entering the same lock through a helper is out of scope
    result = lint_pkg(
        tmp_path,
        {
            "fleet.py": """
            import threading

            from pkg.engine import Engine


            class Fleet:
                def __init__(self):
                    self._scale_lock = threading.Lock()
                    self._engine = Engine()

                def scale(self):
                    with self._scale_lock:
                        self._engine.drain()

                def fast_scale(self):
                    with self._scale_lock:
                        self._engine.drain()
            """,
            "engine.py": """
            import threading


            class Engine:
                def __init__(self):
                    self._lock = threading.Condition()

                def drain(self):
                    with self._lock:
                        self._free_locked()

                def _free_locked(self):
                    pass
            """,
        },
    )
    assert rule_ids(result) == []


def test_tpu010_locked_convention_participates(tmp_path):
    # a *_locked method runs with its class's lock held by contract: calling
    # another class's locking method from it is an edge; the reverse direction
    # in the other module closes the cycle
    result = lint_pkg(
        tmp_path,
        {
            "cache.py": """
            import threading

            from pkg.pool import Pool


            class Cache:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._pool = Pool()

                def _evict_locked(self):
                    self._pool.grab()
            """,
            "pool.py": """
            import threading

            import pkg.cache


            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()

                def grab(self):
                    with self._lock:
                        pass

                def rebalance(self, cache: pkg.cache.Cache):
                    with self._lock:
                        cache._evict_locked()
            """,
        },
    )
    assert rule_ids(result) == ["TPU010"]


def test_tpu010_textually_nested_with_statements(tmp_path):
    # `with self._a:` with `with self._b:` as a SEPARATE nested statement (not
    # the `with a, b:` single-statement form) — the inner acquisition must be
    # recorded with the outer lock held, so opposite nesting in two methods is
    # a cycle
    result = lint_pkg(
        tmp_path,
        {
            "pair.py": """
            import threading


            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def forward(self):
                    with self._a:
                        with self._b:
                            pass

                def backward(self):
                    with self._b:
                        with self._a:
                            pass
            """,
        },
    )
    assert rule_ids(result) == ["TPU010"]
    message = result.findings[0].message
    assert "Pair._a" in message and "Pair._b" in message


def test_tpu010_call_under_nested_with_carries_inner_lock(tmp_path):
    # a call under the INNER of two textually nested withs must carry both
    # locks in its held-set: the b -> c edge exists only because the
    # grab_c() call site holds _b, and backward's c -> b closes the cycle
    result = lint_pkg(
        tmp_path,
        {
            "trio.py": """
            import threading


            class Trio:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self._c = threading.Lock()

                def forward(self):
                    with self._a:
                        with self._b:
                            self.grab_c()

                def grab_c(self):
                    with self._c:
                        pass

                def backward(self):
                    with self._c, self._b:
                        pass
            """,
        },
    )
    assert rule_ids(result) == ["TPU010"]
    assert "Trio._b" in result.findings[0].message and "Trio._c" in result.findings[0].message


def test_tpu011_flags_varying_static_args_cross_module(tmp_path):
    result = lint_pkg(
        tmp_path,
        {
            "kernels.py": """
            import functools

            import jax


            @functools.partial(jax.jit, static_argnames=("steps",))
            def decode(params, carry, steps):
                return carry


            @functools.partial(jax.jit, static_argnums=(1,))
            def gather(rows, width):
                return rows
            """,
            "serve.py": """
            from pkg.kernels import decode, gather


            def storm(params, carry, prompt):
                out = carry
                for n in range(10):
                    out = decode(params, out, steps=n)
                return gather(out, len(prompt))
            """,
        },
    )
    assert rule_ids(result) == ["TPU011", "TPU011"]
    assert "loop variable 'n'" in result.findings[0].message
    assert "len() of parameter 'prompt'" in result.findings[1].message
    assert "recompile" in result.findings[0].message or "trace+compile" in result.findings[0].message


def test_tpu011_near_miss_constants_and_forwarded_params(tmp_path):
    # module constants, config attributes, and plain forwarded parameters are
    # not provably varying — the classic bucketed-steps call must stay clean
    result = lint_pkg(
        tmp_path,
        {
            "kernels.py": """
            import functools

            import jax


            @functools.partial(jax.jit, static_argnames=("steps",))
            def decode(params, carry, steps):
                return carry
            """,
            "serve.py": """
            from pkg.kernels import decode

            CHUNK = 64


            def ok(params, carry, steps):
                out = decode(params, carry, steps=CHUNK)
                out = decode(params, out, steps=steps)
                return out
            """,
        },
    )
    assert rule_ids(result) == []


def test_tpu011_attribute_binding_static_argnums(tmp_path):
    # the engine idiom: self._fn = jax.jit(impl, static_argnums=...) — the
    # hazard is at the method's call site, possibly far from the wrap
    result = lint_pkg(
        tmp_path,
        {
            "engine.py": """
            import jax


            def gather_rows(rows, table, width):
                return rows


            class Engine:
                def __init__(self):
                    self._gather = jax.jit(gather_rows, static_argnums=(2,))

                def admit(self, rows, table, lengths):
                    for length in lengths:
                        rows = self._gather(rows, table, length)
                    return rows
            """,
        },
    )
    assert rule_ids(result) == ["TPU011"]
    assert "loop variable 'length'" in result.findings[0].message


def test_tpu011_nested_for_loops_accumulate_targets(tmp_path):
    # a for directly inside another for (no intervening statement) must still
    # register its own target: the inner loop variable in a static position is
    # the canonical recompile-storm shape
    result = lint_pkg(
        tmp_path,
        {
            "kernels.py": """
            import functools

            import jax


            @functools.partial(jax.jit, static_argnames=("steps",))
            def decode(params, carry, steps):
                return carry
            """,
            "serve.py": """
            from pkg.kernels import decode


            def storm(params, carry, batches):
                out = carry
                for batch in batches:
                    for n in range(4):
                        out = decode(params, out, steps=n)
                return out
            """,
        },
    )
    assert rule_ids(result) == ["TPU011"]
    assert "loop variable 'n'" in result.findings[0].message


def test_tpu011_jit_decorated_method_static_argnums(tmp_path):
    # decorator static_argnums count the unbound `self` (position 2 = width),
    # but the self.gather(...) call site has no receiver argument — the check
    # must look at call position 1, not 2
    result = lint_pkg(
        tmp_path,
        {
            "engine.py": """
            import functools

            import jax


            class Engine:
                @functools.partial(jax.jit, static_argnums=(2,))
                def gather(self, rows, width):
                    return rows

                def admit(self, rows, lengths):
                    for length in lengths:
                        rows = self.gather(rows, length)
                    return rows
            """,
        },
    )
    assert rule_ids(result) == ["TPU011"]
    assert "loop variable 'length'" in result.findings[0].message


def test_tpu012_flags_executor_and_thread_holes_cross_module(tmp_path):
    result = lint_pkg(
        tmp_path,
        {
            "tenancy.py": """
            import contextvars

            _tenant_var = contextvars.ContextVar("tenant", default=None)


            def current_tenant():
                return _tenant_var.get()
            """,
            "handler.py": """
            import threading

            from pkg.tenancy import current_tenant


            def bill_stream():
                return current_tenant()


            async def pull(loop):
                return await loop.run_in_executor(None, bill_stream)


            def spawn():
                threading.Thread(target=bill_stream).start()
            """,
        },
    )
    assert rule_ids(result) == ["TPU012", "TPU012"]
    assert "bill_stream" in result.findings[0].message
    assert "_tenant_var" in result.findings[0].message
    assert "ctx.run" in result.findings[0].message
    assert "Thread target" in result.findings[1].message


def test_tpu012_near_miss_wrapped_and_no_read(tmp_path):
    # the PR 5 fix idiom (ctx.run), a partial(ctx.run, fn) wrap, a target that
    # reads no contextvar, and an unresolvable stored callable — none may flag
    result = lint_pkg(
        tmp_path,
        {
            "tenancy.py": """
            import contextvars

            _tenant_var = contextvars.ContextVar("tenant", default=None)


            def current_tenant():
                return _tenant_var.get()
            """,
            "handler.py": """
            import contextvars
            import functools
            import threading

            from pkg.tenancy import current_tenant


            def bill_stream():
                return current_tenant()


            def plain():
                return 1


            async def wrapped(loop):
                ctx = contextvars.copy_context()
                return await loop.run_in_executor(None, ctx.run, bill_stream)


            def wrapped_thread():
                ctx = contextvars.copy_context()
                threading.Thread(target=functools.partial(ctx.run, bill_stream)).start()


            async def no_read(loop):
                return await loop.run_in_executor(None, plain)


            class Batcher:
                def __init__(self, fn):
                    self._fn = fn

                async def call(self, loop):
                    return await loop.run_in_executor(None, self._fn)
            """,
        },
    )
    assert rule_ids(result) == []


def test_tpu001_cross_module_reachability(tmp_path):
    # the host sync hides in a helper module the jitted entry imports — the
    # per-file pass cannot see it; the index-backed pass must
    result = lint_pkg(
        tmp_path,
        {
            "helpers.py": """
            import numpy as np


            def to_host(y):
                return np.asarray(y)
            """,
            "main.py": """
            import jax

            from pkg.helpers import to_host


            @jax.jit
            def entry(y):
                return to_host(y)
            """,
        },
    )
    assert rule_ids(result) == ["TPU001"]
    assert result.findings[0].path.endswith("helpers.py")
    assert "np.asarray" in result.findings[0].message


def test_tpu001_cross_module_near_miss_unreachable_helper(tmp_path):
    # same helper, never called from a jit entry: ordinary host code
    result = lint_pkg(
        tmp_path,
        {
            "helpers.py": """
            import numpy as np


            def to_host(y):
                return np.asarray(y)
            """,
            "main.py": """
            import jax

            from pkg.helpers import to_host


            @jax.jit
            def entry(y):
                return y + 1


            def host_side(y):
                return to_host(y)
            """,
        },
    )
    assert rule_ids(result) == []


def test_tpu002_cross_module_donor(tmp_path):
    # the donor is decorated in kernels.py; train.py imports and misuses it —
    # reading `state` after its buffer was donated, two modules away
    result = lint_pkg(
        tmp_path,
        {
            "kernels.py": """
            from functools import partial

            import jax


            @partial(jax.jit, donate_argnums=(0,))
            def update(carry, x):
                return carry + x
            """,
            "train.py": """
            from pkg.kernels import update


            def train(state, xs):
                for x in xs:
                    out = update(state, x)
                return state


            def train_ok(state, xs):
                for x in xs:
                    state = update(state, x)
                return state
            """,
        },
    )
    # two findings in train(): the loop back edge carries the donation into
    # the next iteration's `update(state, x)`, and it reaches `return state`
    assert rule_ids(result) == ["TPU002", "TPU002"]
    assert all(f.path.endswith("train.py") for f in result.findings)
    assert all("'state'" in f.message for f in result.findings)


def test_project_rule_findings_respect_suppressions(tmp_path):
    result = lint_pkg(
        tmp_path,
        {
            "helpers.py": """
            import numpy as np


            def to_host(y):
                return np.asarray(y)  # tpu-lint: disable=TPU001
            """,
            "main.py": """
            import jax

            from pkg.helpers import to_host


            @jax.jit
            def entry(y):
                return to_host(y)
            """,
        },
    )
    assert rule_ids(result) == []
    assert [finding.rule for finding in result.suppressed] == ["TPU001"]


# --------------------------------------------------------------------- TPU013


def test_tpu013_flags_collective_under_lock(tmp_path):
    # the three spellings: a with-block collective, a *_locked method body
    # (caller holds the lock), and a control-plane RPC on a host handle
    result = lint_source(
        tmp_path,
        """
        import threading

        from jax.experimental import multihost_utils


        class Coordinator:
            def __init__(self):
                self._lock = threading.Lock()
                self.hosts = []

            def rebalance(self):
                with self._lock:
                    multihost_utils.sync_global_devices("rebalance")

            def _sync_locked(self):
                broadcast_one_to_all(None)

            def route(self, i):
                with self._lock:
                    self.hosts[i].probe([1, 2])
        """,
    )
    assert rule_ids(result) == ["TPU013", "TPU013", "TPU013"]
    assert "multihost_utils.sync_global_devices" in result.findings[0].message
    assert "self._lock" in result.findings[0].message
    assert "broadcast_one_to_all" in result.findings[1].message
    assert "probe" in result.findings[2].message


def test_tpu013_flags_jax_distributed_and_repo_helpers(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import threading

        import jax
        from unionml_tpu import distributed


        class Fleet:
            def __init__(self):
                self._state_lock = threading.Condition()

            def join(self):
                with self._state_lock:
                    jax.distributed.initialize()

            def agree_config(self, cfg):
                with self._state_lock:
                    return distributed.agree(cfg)
        """,
    )
    assert rule_ids(result) == ["TPU013", "TPU013"]
    assert "jax.distributed.initialize" in result.findings[0].message
    assert "distributed.agree" in result.findings[1].message


def test_tpu013_near_miss_outside_lock_and_lockless_class(tmp_path):
    # the fix idiom (snapshot under the lock, rendezvous outside), collectives
    # in a class with no lock, ordinary calls under the lock, and __init__ are
    # all clean
    result = lint_source(
        tmp_path,
        """
        import threading

        from jax.experimental import multihost_utils


        class Coordinator:
            def __init__(self):
                self._lock = threading.Lock()
                multihost_utils.sync_global_devices("construction")  # pre-sharing

            def rebalance(self):
                with self._lock:
                    plan = self._plan()
                multihost_utils.sync_global_devices("rebalance")
                return plan

            def _plan(self):
                with self._lock:
                    return len("plan")


        class LockFree:
            def sync(self):
                multihost_utils.sync_global_devices("fine")
        """,
    )
    assert result.findings == []


# ------------------------------------------------- index cache + incremental


def test_index_cache_invalidation_on_edit(tmp_path):
    snippet = tmp_path / "snippet.py"
    snippet.write_text("x = 1\n")
    first = run_lint([snippet])
    assert first.clean and first.index_stats == {"hits": 0, "misses": 1}
    warm = run_lint([snippet])
    assert warm.index_stats == {"hits": 1, "misses": 0}
    # the edit introduces a violation: the stale cached summary/findings must
    # be dropped on the content-hash mismatch
    snippet.write_text("import os\nA = int(os.environ['A'])\n")
    edited = run_lint([snippet])
    assert edited.index_stats == {"hits": 0, "misses": 1}
    assert rule_ids(edited) == ["TPU005"]
    # and a fix is picked up the same way
    snippet.write_text("x = 2\n")
    assert run_lint([snippet]).clean


def test_run_lint_only_reports_named_files_with_whole_program_index(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "helpers.py").write_text(
        textwrap.dedent(
            """
            import numpy as np


            def to_host(y):
                return np.asarray(y)
            """
        )
    )
    (pkg / "main.py").write_text(
        textwrap.dedent(
            """
            import os

            import jax

            from pkg.helpers import to_host

            A = int(os.environ["A"])


            @jax.jit
            def entry(y):
                return to_host(y)
            """
        )
    )
    # only= restricts REPORTING, not the index: helpers.py's TPU001 finding
    # (which needs main.py's jit entry to exist) is filtered out, main.py's
    # TPU005 stays
    result = run_lint([pkg], only=[pkg / "main.py"])
    assert rule_ids(result) == ["TPU005"]
    assert result.files == 1
    full = run_lint([pkg])
    assert sorted(rule_ids(full)) == ["TPU001", "TPU005"]


def test_changed_only_cli_against_git(tmp_path, monkeypatch, capsys):
    import subprocess

    repo = tmp_path / "repo"
    repo.mkdir()
    git = lambda *args: subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=repo,
        check=True,
        capture_output=True,
    )
    git("init", "-q")
    (repo / "stable.py").write_text("import os\nB = int(os.environ['B'])\n")
    (repo / "touched.py").write_text("x = 1\n")
    git("add", ".")
    git("commit", "-q", "-m", "seed")
    (repo / "touched.py").write_text("import os\nA = int(os.environ['A'])\n")
    monkeypatch.chdir(repo)
    # full run sees both findings; --changed-only reports just the edited file
    assert lint_main([str(repo)]) == 1
    capsys.readouterr()
    assert lint_main([str(repo), "--changed-only", "HEAD", "--format", "json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["counts"] == {"TPU005": 1}
    assert payload["findings"][0]["path"].endswith("touched.py")
    assert payload["files"] == 1


# ----------------------------------------------------------- SARIF reporter


def test_sarif_reporter_round_trip(tmp_path):
    from unionml_tpu.analysis import render_sarif

    result = lint_source(
        tmp_path,
        """
        import os

        A = int(os.environ.get("A", "0"))
        B = int(os.environ.get("B", "0"))  # tpu-lint: disable=TPU005
        """,
    )
    payload = json.loads(render_sarif(result))
    assert payload["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in payload["$schema"]
    run = payload["runs"][0]
    assert run["tool"]["driver"]["name"] == "tpu-lint"
    rule_index = {rule["id"] for rule in run["tool"]["driver"]["rules"]}
    assert {"TPU001", "TPU005", "TPU010", "TPU011", "TPU012", "TPU013"} <= rule_index
    active = [r for r in run["results"] if "suppressions" not in r]
    suppressed = [r for r in run["results"] if "suppressions" in r]
    assert len(active) == 1 and len(suppressed) == 1
    assert active[0]["ruleId"] == "TPU005"
    region = active[0]["locations"][0]["physicalLocation"]["region"]
    assert region["startLine"] == 4 and region["startColumn"] >= 1
    assert suppressed[0]["suppressions"] == [
        {"kind": "inSource", "justification": "# tpu-lint: disable"}
    ]
    assert run["invocations"][0]["executionSuccessful"] is True


def test_sarif_cli_format(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nA = int(os.environ['A'])\n")
    assert lint_main([str(bad), "--format", "sarif"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["version"] == "2.1.0"
    assert payload["runs"][0]["results"][0]["ruleId"] == "TPU005"
    # JSON schema version is untouched by the SARIF addition
    assert lint_main([str(bad), "--format", "json"]) == 1
    assert json.loads(capsys.readouterr().out)["version"] == 1


# --------------------------------------------------------------------- TPU014


def _lint_bench_source(tmp_path, source):
    """TPU014 is path-scoped to benchmarks/ and workloads/: write the snippet
    under a benchmarks dir so the rule engages."""
    bench_dir = tmp_path / "benchmarks"
    bench_dir.mkdir(exist_ok=True)
    snippet = bench_dir / "bench_snippet.py"
    snippet.write_text(textwrap.dedent(source))
    return run_lint([snippet])


def test_tpu014_flags_global_rng_draws_in_benchmarks(tmp_path):
    result = _lint_bench_source(
        tmp_path,
        """
        import random

        import numpy as np


        def arrivals(n):
            offsets = [random.expovariate(2.0) for _ in range(n)]
            prompts = np.random.randint(1, 90, size=8)
            random.shuffle(offsets)
            return offsets, prompts
        """,
    )
    assert rule_ids(result) == ["TPU014", "TPU014", "TPU014"]
    assert "random.expovariate" in result.findings[0].message
    assert "np.random.randint" in result.findings[1].message
    assert "random.Random(seed)" in result.findings[0].message  # the fix idiom


def test_tpu014_seeded_generators_and_jax_keys_stay_clean(tmp_path):
    # the fixed forms: Random(seed) instances, default_rng(seed) Generators,
    # jax.random keys — and rng METHOD calls are never confused with module
    # draws
    result = _lint_bench_source(
        tmp_path,
        """
        import random

        import jax
        import numpy as np


        def arrivals(n, seed):
            rng = random.Random(seed)
            gen = np.random.default_rng(seed)
            key = jax.random.PRNGKey(seed)
            offsets = [rng.expovariate(2.0) for _ in range(n)]
            prompts = gen.integers(1, 90, size=8)
            noise = jax.random.normal(key, (4,))
            return offsets, prompts, noise
        """,
    )
    assert rule_ids(result) == []


def test_tpu014_out_of_scope_paths_stay_clean(tmp_path):
    # the same global draw OUTSIDE benchmarks/workloads is out of scope:
    # library code that wants entropy (id minting) is not the rule's business
    result = lint_source(
        tmp_path,
        """
        import random


        def jitter():
            return random.random()
        """,
    )
    assert rule_ids(result) == []


def test_tpu014_workloads_scope_and_global_seed(tmp_path):
    # unionml_tpu/workloads is in scope too, and global random.seed() — the
    # "seeded but shared" trap — is flagged alongside the draws
    wl = tmp_path / "workloads"
    wl.mkdir()
    snippet = wl / "scenario.py"
    snippet.write_text(textwrap.dedent(
        """
        import random


        def build(seed):
            random.seed(seed)
            return [random.randrange(90) for _ in range(4)]
        """
    ))
    result = run_lint([snippet])
    assert rule_ids(result) == ["TPU014", "TPU014"]
    assert "random.seed" in result.findings[0].message


# --------------------------------------------------------------------- TPU015


def test_tpu015_flags_unbounded_retry_loops(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import itertools
        from urllib.request import urlopen


        def hammer(host):
            while True:
                try:
                    host.ping()
                    break
                except OSError:
                    continue


        def hammer_http(url):
            for _ in itertools.count():
                urlopen(url)
        """,
    )
    assert rule_ids(result) == ["TPU015", "TPU015"]
    assert "host.ping" in result.findings[0].message
    assert "_call_retry" in result.findings[0].message  # the fix idiom
    assert "urlopen" in result.findings[1].message


def test_tpu015_bounded_and_paced_loops_stay_clean(tmp_path):
    # the three brakes: a bounded for-range envelope (the
    # RemoteHost._call_retry shape), a Compare-bounded while (attempt counter
    # or deadline), and an Event.wait-paced polling loop — plus the walk of a
    # finite host list, which is one attempt per host, not a retry
    result = lint_source(
        tmp_path,
        """
        import time


        def walk(hosts, prompt):
            for host in hosts:
                host.probe(prompt)


        def bounded_envelope(host):
            for attempt in range(3):
                try:
                    return host.ping()
                except OSError:
                    time.sleep(0.05 * (attempt + 1))


        def deadline_bounded(host, deadline, clock):
            while clock() < deadline:
                try:
                    return host.ping()
                except OSError:
                    time.sleep(0.1)


        class Reconciler:
            def loop(self):
                while not self._stop.wait(0.2):
                    self.hosts[0].ping()
        """,
    )
    assert rule_ids(result) == []


def test_tpu015_sleepless_while_true_without_network_stays_clean(tmp_path):
    # unbounded loops that never touch the network are some other rule's
    # business (a decode engine's dispatch loop, a queue drain)
    result = lint_source(
        tmp_path,
        """
        def drain(queue):
            while True:
                item = queue.get()
                if item is None:
                    return
        """,
    )
    assert rule_ids(result) == []


def test_tpu015_nested_def_does_not_leak_pacing_or_calls(tmp_path):
    # a sleep INSIDE a nested function does not pace the outer loop, and a
    # network call inside a nested function is not the loop's call
    result = lint_source(
        tmp_path,
        """
        import time


        def bad(host):
            while True:
                def later():
                    time.sleep(1.0)
                host.ping()


        def clean(host):
            while True:
                def work():
                    host.ping()
                register(work)
                if done():
                    return
        """,
    )
    assert rule_ids(result) == ["TPU015"]


# ------------------------------------------------------- CFG construction


def _cfg_of(source, name="f"):
    import ast

    from unionml_tpu.analysis.cfg import build_cfg

    tree = ast.parse(textwrap.dedent(source))
    func = next(
        n
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) and n.name == name
    )
    return build_cfg(func)


def _nodes_calling(cfg, fname):
    import ast

    out = []
    for node in cfg.statement_nodes():
        for expr in node.exprs:
            if expr is None:
                continue
            for sub in ast.walk(expr):
                if (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Name)
                    and sub.func.id == fname
                ):
                    out.append(node)
    return out


def test_cfg_try_finally_with_return_threads_the_finally():
    # the finally body runs on the return path: the release node's successors
    # reach function EXIT, and no path skips it
    cfg = _cfg_of(
        """
        def f(x, release):
            try:
                return x
            finally:
                release()
        """
    )
    releases = _nodes_calling(cfg, "release")
    assert releases, "finally body missing from CFG"
    assert any(
        dst == cfg.exit for node in releases for dst, _ in node.succs
    ), "return continuation does not pass through the finally"


def test_cfg_try_finally_with_break_exits_the_loop():
    # break inside try/finally: the finally copy on the break continuation
    # leads OUT of the loop (to `done()`), not back to the header
    cfg = _cfg_of(
        """
        def f(items, release, done):
            for item in items:
                try:
                    break
                finally:
                    release()
            done()
        """
    )
    done_nids = {n.nid for n in _nodes_calling(cfg, "done")}
    assert done_nids
    assert any(
        dst in done_nids for node in _nodes_calling(cfg, "release") for dst, _ in node.succs
    ), "break continuation does not leave the loop after the finally"


def test_cfg_nested_handlers_with_reraise_route_to_outer_catch_all():
    # the inner handler's bare `raise` lands in the OUTER handler; with the
    # outer being a catch-all and nothing else raising, the function cannot
    # terminate by exception
    cfg = _cfg_of(
        """
        def f(work):
            try:
                try:
                    work()
                except ValueError:
                    raise
            except Exception:
                x = 1
        """
    )
    assert cfg.nodes[cfg.raise_node].preds == []


def test_cfg_with_tuple_target_and_split_exits():
    import ast

    # `with make() as (a, b):` — both names are bound at the with header, and
    # the splitting-style __exit__ gives the normal and exception
    # continuations their own with_exit nodes
    cfg = _cfg_of(
        """
        def f(make, use):
            with make() as (a, b):
                use(a, b)
        """
    )
    header = next(n for n in cfg.statement_nodes() if isinstance(n.stmt, ast.With))
    bound = {
        sub.id
        for expr in header.exprs
        if expr is not None
        for sub in ast.walk(expr)
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store)
    }
    assert bound == {"a", "b"}
    exits = [n for n in cfg.statement_nodes() if n.kind == "with_exit"]
    assert len(exits) == 2  # one for normal completion, one for the exc path
    kinds = {kind for n in exits for _, kind in n.succs}
    assert "exc" in kinds  # the exception continuation keeps raising


def test_cfg_while_else_runs_on_normal_exit():
    cfg = _cfg_of(
        """
        def f(n, finish, after):
            while n > 0:
                n -= 1
            else:
                finish()
            after()
        """
    )
    assert cfg.back_edges, "loop has no back edge"
    finish = _nodes_calling(cfg, "finish")
    assert finish, "while/else body missing"
    # else runs off the loop's FALSE edge, then falls through to after()
    assert any(kind == "false" for _, kind in finish[0].preds)
    after_nids = {n.nid for n in _nodes_calling(cfg, "after")}
    assert any(dst in after_nids for dst, _ in finish[0].succs)


def test_cfg_yield_inside_with_is_a_marked_suspension():
    cfg = _cfg_of(
        """
        def f(lock):
            with lock:
                yield 1
        """
    )
    yields = [n for n in cfg.statement_nodes() if n.is_yield]
    assert len(yields) == 1
    # the suspension sits between the with header and its exit
    import ast

    header = next(n for n in cfg.statement_nodes() if isinstance(n.stmt, ast.With))
    assert any(src == header.nid for src, _ in yields[0].preds)


# ------------------------------------------------------- dataflow + dominators


def test_dataflow_exception_edge_drops_the_statements_own_gen():
    # acquire-style fact: generated when the statement COMPLETES, so the exc
    # edge out of the generating statement must not carry it
    import ast

    from unionml_tpu.analysis.dataflow import Problem, solve_forward

    cfg = _cfg_of(
        """
        def f(acquire, use):
            h = acquire()
            use(h)
        """
    )

    class Acquired(Problem):
        def gen_kill(self, node):
            gen = set()
            if node.stmt is not None and isinstance(node.stmt, ast.Assign):
                gen.add("h")
            return gen, set()

    sol = solve_forward(cfg, Acquired())
    use_node = _nodes_calling(cfg, "use")[0]
    assert "h" in sol.in_facts(use_node.nid)  # normal path has the fact
    # but the exception exit only sees facts from use(h)'s OWN exc edge —
    # the assign's exc edge (acquire() itself raised) carries nothing
    assert sol.at_raise == frozenset({"h"})


def test_dominators_branch_join():
    import ast

    from unionml_tpu.analysis.dataflow import dominators

    cfg = _cfg_of(
        """
        def f(cond, a, b, join):
            if cond:
                a()
            else:
                b()
            join()
        """
    )
    dom = dominators(cfg)
    header = next(n for n in cfg.statement_nodes() if isinstance(n.stmt, ast.If))
    a_node = _nodes_calling(cfg, "a")[0]
    join_node = _nodes_calling(cfg, "join")[0]
    assert header.nid in dom[join_node.nid]  # the test runs on every path
    assert a_node.nid not in dom[join_node.nid]  # one branch does not
    assert join_node.nid in dom[join_node.nid]  # reflexive


# --------------------------------------------------------------------- TPU016


def test_tpu016_flags_connection_leaked_on_exception_path(tmp_path):
    # request()/getresponse() can raise after the connection exists — without
    # a try/except-close the socket leaks on every error
    result = lint_source(
        tmp_path,
        """
        from http.client import HTTPConnection

        def fetch(host, payload):
            conn = HTTPConnection(host)
            conn.request("POST", "/step", payload)
            resp = conn.getresponse()
            body = resp.read()
            conn.close()
            return body
        """,
    )
    assert "TPU016" in rule_ids(result)
    assert "conn" in result.findings[0].message


def test_tpu016_near_miss_guarded_and_with_managed(tmp_path):
    # the two clean shapes: close in an except-reraise guard, and the context
    # manager (guaranteed release through with_exit on every continuation)
    result = lint_source(
        tmp_path,
        """
        from http.client import HTTPConnection

        def fetch(host, payload):
            conn = HTTPConnection(host)
            try:
                conn.request("POST", "/step", payload)
                body = conn.getresponse().read()
            except BaseException:
                conn.close()
                raise
            conn.close()
            return body

        def read_config(path):
            with open(path) as handle:
                return handle.read()
        """,
    )
    assert rule_ids(result) == []


# --------------------------------------------------------------------- TPU017


def test_tpu017_flags_charge_without_refund_on_exception(tmp_path):
    result = lint_source(
        tmp_path,
        """
        def submit(registry, tenant, grammar, compile_grammar):
            retry_after = registry.try_admit(tenant)
            if retry_after is not None:
                raise RuntimeError("throttled")
            compile_grammar(grammar)
            return True
        """,
    )
    assert rule_ids(result) == ["TPU017"]
    assert "refund" in result.findings[0].message


def test_tpu017_near_miss_refund_in_except_and_shed_path(tmp_path):
    # the canonical shapes stay clean: refund-and-reraise, and the shed path
    # (non-None retry_after means the bucket was NOT debited)
    result = lint_source(
        tmp_path,
        """
        def submit(registry, tenant, grammar, compile_grammar):
            retry_after = registry.try_admit(tenant)
            if retry_after is not None:
                raise RuntimeError("throttled")
            try:
                compile_grammar(grammar)
            except BaseException:
                registry.refund(tenant)
                raise
            return True
        """,
    )
    assert rule_ids(result) == []


# --------------------------------------------------------------------- TPU018


def test_tpu018_flags_yield_while_holding_lock(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import threading

        class Streamer:
            def __init__(self):
                self._lock = threading.Lock()

            def stream(self, chunks):
                with self._lock:
                    for chunk in chunks:
                        yield chunk
        """,
    )
    assert "TPU018" in rule_ids(result)


def test_tpu018_near_miss_snapshot_then_yield(tmp_path):
    # copy under the lock, yield outside it — the consumer can stall forever
    # without holding up writers
    result = lint_source(
        tmp_path,
        """
        import threading

        class Streamer:
            def __init__(self):
                self._lock = threading.Lock()
                self._chunks = []

            def stream(self):
                with self._lock:
                    snapshot = list(self._chunks)
                for chunk in snapshot:
                    yield chunk
        """,
    )
    assert rule_ids(result) == []


# --------------------------------------------------------------------- TPU019


def test_tpu019_flags_early_return_leaking_handle(tmp_path):
    result = lint_source(
        tmp_path,
        """
        def read_config(path, strict):
            handle = open(path)
            if strict:
                return None
            handle.close()
            return True
        """,
    )
    assert rule_ids(result) == ["TPU019"]


def test_tpu019_near_miss_returning_the_resource_or_closing_first(tmp_path):
    # returning the handle transfers ownership to the caller; closing before
    # the early return is the fix the rule asks for
    result = lint_source(
        tmp_path,
        """
        def open_config(path, strict):
            handle = open(path)
            if strict:
                return handle
            handle.close()
            return None

        def peek_config(path, strict):
            handle = open(path)
            if strict:
                handle.close()
                return None
            handle.close()
            return True
        """,
    )
    assert rule_ids(result) == []


# --------------------------------------- TPU015 dominance of the in-body bound


def test_tpu015_in_body_bound_dominating_the_back_edge_is_clean(tmp_path):
    result = lint_source(
        tmp_path,
        """
        def reconnect(host):
            attempt = 0
            while True:
                resp = host.ping()
                if resp:
                    return resp
                if attempt >= 5:
                    raise RuntimeError("gave up")
                attempt += 1
        """,
    )
    assert rule_ids(result) == []


def test_tpu015_bound_buried_under_rare_flag_still_flags(tmp_path):
    # the bound test only runs when `flag` flips — it does not dominate the
    # back edge, so the loop is effectively unbounded
    result = lint_source(
        tmp_path,
        """
        def reconnect(host, flag):
            attempt = 0
            while True:
                resp = host.ping()
                if flag:
                    if attempt >= 5:
                        break
                attempt += 1
        """,
    )
    assert rule_ids(result) == ["TPU015"]


# ----------------------------------------------------- baseline + disable-file


def test_baseline_records_then_reports_only_new(tmp_path, capsys):
    target = tmp_path / "mod.py"
    target.write_text(
        textwrap.dedent(
            """
            import os

            A = int(os.environ.get("A", "0"))
            """
        )
    )
    baseline = tmp_path / "lint-baseline.json"
    assert (
        lint_main([str(target), "--baseline", str(baseline), "--update-baseline"]) == 0
    )
    capsys.readouterr()
    # known finding absorbed; exit 0 even though the finding still exists
    assert lint_main([str(target), "--baseline", str(baseline)]) == 0
    out = capsys.readouterr().out
    assert "0 finding(s)" in out and "1 baselined" in out
    # a NEW finding (second env read) still fails the gate
    target.write_text(target.read_text() + 'B = int(os.environ.get("B", "0"))\n')
    assert lint_main([str(target), "--baseline", str(baseline)]) == 1
    out = capsys.readouterr().out
    assert "1 finding(s)" in out and "1 baselined" in out


def test_baseline_missing_file_is_a_usage_error(tmp_path, capsys):
    target = tmp_path / "mod.py"
    target.write_text("x = 1\n")
    assert lint_main([str(target), "--baseline", str(tmp_path / "absent.json")]) == 2
    assert "does not exist" in capsys.readouterr().err


def test_baseline_sarif_carries_baseline_state(tmp_path, capsys):
    target = tmp_path / "mod.py"
    target.write_text(
        textwrap.dedent(
            """
            import os

            A = int(os.environ.get("A", "0"))
            """
        )
    )
    baseline = tmp_path / "bl.json"
    lint_main([str(target), "--baseline", str(baseline), "--update-baseline"])
    capsys.readouterr()
    target.write_text(target.read_text() + 'B = int(os.environ.get("B", "0"))\n')
    lint_main([str(target), "--baseline", str(baseline), "--format", "sarif"])
    payload = json.loads(capsys.readouterr().out)
    states = sorted(r["baselineState"] for r in payload["runs"][0]["results"])
    assert states == ["new", "unchanged"]


def test_disable_file_suppresses_both_passes(tmp_path):
    # per-file rule (TPU005) and project rule (TPU017) both honor the header
    # comment; the un-listed rule still fires
    result = lint_pkg(
        tmp_path,
        {
            "mod.py": """
            # tpu-lint: disable-file=TPU005, TPU017
            import os

            A = int(os.environ.get("A", "0"))

            def submit(registry, tenant, work):
                retry_after = registry.try_admit(tenant)
                if retry_after is not None:
                    raise RuntimeError("throttled")
                work()
                return True
            """,
        },
    )
    assert rule_ids(result) == []
    assert sorted(f.rule for f in result.suppressed) == ["TPU005", "TPU017"]


def test_disable_file_only_honored_in_first_five_lines(tmp_path):
    result = lint_source(
        tmp_path,
        """
        import os

        A = 1
        B = 2
        C = 3
        # tpu-lint: disable-file=TPU005
        D = int(os.environ.get("D", "0"))
        """,
    )
    assert rule_ids(result) == ["TPU005"]
    assert result.suppressed == []
