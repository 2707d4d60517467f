"""reprolint: every rule fires on a seeded violation and stays silent on
the matching compliant snippet; suppressions and baselines behave; the
shipped tree is clean; and the PR 7 gateway busy-spin shape — the bug
the async_draining fix removed — is flagged as a regression fixture.

Fixtures go through ``lint_source`` with a repo-shaped ``filename`` so
the path-scoped rules (RL001 hot files, RL005 serving/) engage."""

import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from tools.reprolint import (  # noqa: E402
    RULES, lint_paths, lint_source, load_baseline, save_baseline,
)

ENGINE = "src/repro/serving/engine.py"  # hot-path + serving/ scoped


def codes(findings):
    return [f.rule for f in findings]


def lint(src, filename=ENGINE):
    return lint_source(textwrap.dedent(src), filename=filename)


# --------------------------------------------------------------------------- #
# RL001 host-sync-in-hot-path
# --------------------------------------------------------------------------- #
TIMED_SYNC = """
    import time
    import numpy as np

    def _prefill(self, rec, toks):
        t0 = time.perf_counter()
        host = np.asarray(toks)           # device->host sync, timed stage
        rec.add("preprocess", time.perf_counter() - t0)
        return host
"""


def test_rl001_flags_sync_in_timed_stage():
    found = lint(TIMED_SYNC)
    # RL007 also fires: the fixture charges a stage with no span emitter
    assert sorted(codes(found)) == ["RL001", "RL007"]
    msg = next(f for f in found if f.rule == "RL001").message
    assert "np.asarray" in msg or "numpy.asarray" in msg


def test_rl001_flags_item_blockuntilready_and_device_int():
    found = lint("""
        import time
        import jax

        def _step(self, rec, x):
            t0 = time.perf_counter()
            x.block_until_ready()
            n = x.item()
            tok = int(jax.numpy.argmax(x))
            rec.add("inference", time.perf_counter() - t0)
            return n, tok
    """)
    assert sorted(codes(found)) == ["RL001", "RL001", "RL001", "RL007"]


def test_rl001_import_alias_does_not_dodge():
    found = lint("""
        import time
        from jax import device_get as dg

        def _drain(self, rec, x):
            t0 = time.perf_counter()
            y = dg(x)
            rec.add("transfer", time.perf_counter() - t0)
            return y
    """)
    assert sorted(codes(found)) == ["RL001", "RL007"]


def test_rl001_silent_on_untimed_and_harvest_and_literals():
    # not a timed-stage function (no stage charge): the designated
    # harvest thread's device_get must stay legal
    assert lint("""
        import jax

        def _harvest_loop(self):
            toks, done = jax.device_get((self.entry.tokens, self.entry.done))
            return toks, done
    """) == []
    # np.asarray over a host literal inside a timed stage is host-only
    # (the _trace_admission call keeps RL007 satisfied so this fixture
    # stays about RL001's silence)
    assert lint("""
        import time
        import numpy as np

        def _admit(self, rec, slot):
            t0 = time.perf_counter()
            idx = np.asarray([slot], np.int32)
            rec.add("preprocess", time.perf_counter() - t0)
            self._trace_admission(rec, t0)
            return idx
    """) == []


def test_rl001_scoped_to_hot_files():
    # identical code outside engine/disagg/cluster is out of scope
    assert lint(TIMED_SYNC, filename="src/repro/serving/loadgen.py") == []


# --------------------------------------------------------------------------- #
# RL002 impure-jit (applies to every file — fixtures use a non-serving
# path so RL005's serving-scoped warm-table check stays out of the way)
# --------------------------------------------------------------------------- #
KERNEL = "src/repro/models/attention.py"


def test_rl002_flags_clock_in_jitted_fn():
    found = lint("""
        import time
        import jax

        def _step_impl(params, cache):
            t0 = time.perf_counter()      # traced once; times nothing
            return cache

        step = jax.jit(_step_impl)
    """, filename=KERNEL)
    assert codes(found) == ["RL002"]
    assert "time.perf_counter" in found[0].message


def test_rl002_flags_lambda_print_and_transitive_callee():
    found = lint("""
        import jax

        f = jax.jit(lambda x: print(x) or x)

        def _helper(x):
            import numpy as np
            return np.random.rand() * x   # host RNG via transitive call

        def _outer(x):
            return _helper(x)

        g = jax.jit(_outer)
    """, filename=KERNEL)
    assert sorted(codes(found)) == ["RL002", "RL002"]
    scopes = {f.scope for f in found}
    assert "_helper" in scopes  # reached through _outer, not directly jitted


def test_rl002_flags_self_mutation_and_decorator_form():
    found = lint("""
        import functools
        import jax

        class Pool:
            @functools.partial(jax.jit, static_argnums=(0,))
            def _step(self, cache):
                self.calls += 1           # mutates at trace time only
                return cache
    """, filename=KERNEL)
    assert codes(found) == ["RL002"]
    assert "self.calls" in found[0].message


def test_rl002_silent_on_pure_jit_and_host_side_time():
    assert lint("""
        import time
        import jax
        import jax.numpy as jnp

        def _step_impl(params, cache, key):
            key, sub = jax.random.split(key)       # in-jit PRNG is fine
            return cache + jnp.float32(1), key

        step = jax.jit(_step_impl)

        def harvest(self, rec):
            t0 = time.perf_counter()               # NOT jitted: fine
            return t0
    """, filename=KERNEL) == []


# --------------------------------------------------------------------------- #
# RL003 lock discipline
# --------------------------------------------------------------------------- #
def test_rl003_flags_unguarded_access_and_blocking_put_under_lock():
    found = lint("""
        import queue as queue_mod
        import threading

        class EnginePipeline:
            _REPROLINT_GUARDED = ("_outputs", "emitted")

            def __init__(self, backlog):
                self._lock = threading.RLock()
                self._q = queue_mod.Queue(maxsize=backlog)
                self._outputs = []
                self.emitted = 0

            def bad_read(self):
                return len(self._outputs)          # no lock held

            def bad_put(self, item):
                with self._lock:
                    self._q.put(item)              # bounded put under lock
                    self.emitted += 1
    """)
    assert codes(found) == ["RL003", "RL003"]
    assert any("_outputs" in f.message and "outside" in f.message
               for f in found)
    assert any("_q.put" in f.message for f in found)


def test_rl003_flags_blocking_helper_called_under_lock():
    found = lint("""
        import queue as queue_mod
        import threading

        class EnginePipeline:
            _REPROLINT_GUARDED = ("_outstanding",)

            def __init__(self):
                self._lock = threading.RLock()
                self._q = queue_mod.Queue(maxsize=2)
                self._outstanding = 0

            def _put(self, q, item):
                q.put(item, timeout=0.05)

            def dispatch(self, entry):
                with self._lock:
                    self._outstanding += 1
                    self._put(self._q, entry)      # helper blocks
    """)
    assert codes(found) == ["RL003"]
    assert "_put" in found[0].message


def test_rl003_silent_on_disciplined_pipeline_and_undeclared_class():
    # the shipped shape: guarded state under the lock, puts outside it
    assert lint("""
        import queue as queue_mod
        import threading

        class EnginePipeline:
            _REPROLINT_GUARDED = ("_outputs",)

            def __init__(self, backlog):
                self._lock = threading.RLock()
                self._q = queue_mod.Queue(maxsize=backlog)
                self._outputs = []

            def dispatch(self, entry):
                with self._lock:
                    self._outputs.append(entry)
                self._q.put(entry)                 # outside the lock: ok
    """) == []
    # classes without a _REPROLINT_GUARDED declaration are out of scope
    assert lint("""
        class Plain:
            def touch(self):
                return self._outputs
    """) == []


# --------------------------------------------------------------------------- #
# RL004 IPC frame safety
# --------------------------------------------------------------------------- #
def test_rl004_flags_params_and_jax_values_in_frames():
    found = lint("""
        from repro.serving import ipc

        def serve(sock, pipe, params):
            ipc.send_msg(sock, "ok", {"params": params})
    """, filename="src/repro/serving/worker.py")
    assert codes(found) == ["RL004"]
    found = lint("""
        import jax
        from repro.serving.ipc import send_msg

        def snapshot(sock, pipe):
            send_msg(sock, "ok", jax.device_get(pipe.engine.caches))
    """, filename="src/repro/serving/worker.py")
    assert codes(found) == ["RL004"]


def test_rl004_traces_one_level_through_local_helpers():
    found = lint("""
        from repro.serving import ipc

        def _snapshot(pipe):
            return {"caches": pipe.engine.caches}

        def serve(sock, pipe):
            ipc.send_msg(sock, "ok", _snapshot(pipe))
    """, filename="src/repro/serving/worker.py")
    assert codes(found) == ["RL004"]
    assert "_snapshot" in found[0].message


def test_rl004_silent_on_scalar_payloads():
    assert lint("""
        import time
        import jax
        from repro.serving import ipc

        def serve(sock, pipe):
            ipc.send_msg(sock, "ok", {
                "t_child": time.perf_counter(),
                "devices": jax.device_count(),     # host int, not an array
                "emitted": pipe.emitted,
            })
    """, filename="src/repro/serving/worker.py") == []


# --------------------------------------------------------------------------- #
# RL005 warmup coverage
# --------------------------------------------------------------------------- #
def test_rl005_flags_unregistered_jit_in_serving():
    found = lint("""
        import jax

        WARM_PRETRACE_TABLE = frozenset({"_step_jit"})

        class Pool:
            def __init__(self, impl):
                self._step_jit = jax.jit(impl)
                self._rogue_jit = jax.jit(impl)    # not in the table
    """)
    assert codes(found) == ["RL005"]
    assert "_rogue_jit" in found[0].message


def test_rl005_silent_when_registered_or_suppressed_or_outside_serving():
    assert lint("""
        import jax

        WARM_PRETRACE_TABLE = frozenset({"_step_jit", "_splice_jit"})

        class Pool:
            def __init__(self, impl):
                self._step_jit = jax.jit(impl)
                self._splice_jit = jax.jit(impl, donate_argnums=(0,))
                self._legacy = jax.jit(impl)  # reprolint: disable=RL005 legacy retraces by design
    """) == []
    # jits outside serving/ (kernels, tests) are out of scope
    assert lint("""
        import jax

        def make(impl):
            return jax.jit(impl)
    """, filename="src/repro/models/attention.py") == []


# --------------------------------------------------------------------------- #
# RL006 swallowed-failure hygiene
# --------------------------------------------------------------------------- #
def test_rl006_flags_bare_except_and_unguarded_daemon():
    found = lint("""
        import threading

        class Pipeline:
            def _loop(self):
                while True:
                    self.tick()                    # no failure capture

            def start(self):
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()

            def close(self):
                try:
                    self.sock.close()
                except:
                    pass
    """)
    assert sorted(codes(found)) == ["RL006", "RL006"]
    assert any("bare `except:`" in f.message for f in found)
    assert any("_loop" in f.message for f in found)


def test_rl006_silent_on_guarded_runner_and_typed_except():
    assert lint("""
        import threading
        import traceback

        class Pipeline:
            def _run_guarded(self, fn):
                try:
                    fn()
                except BaseException:
                    self._exc = traceback.format_exc()
                    self._stop.set()

            def start(self, fn):
                t = threading.Thread(target=self._run_guarded,
                                     args=(fn,), daemon=True)
                t.start()

            def close(self):
                try:
                    self.sock.close()
                except Exception:
                    pass                           # typed: out of scope
    """) == []


# --------------------------------------------------------------------------- #
# regression fixture: PR 7's gateway busy-spin poll shape
# --------------------------------------------------------------------------- #
def test_pr7_gateway_busy_spin_regression_is_flagged():
    """Before the async_draining fix, the gateway's drain loop busy-spun:
    a timed poll loop synced the device every iteration, and its watchdog
    daemon swallowed failures behind a bare except. Reintroducing that
    shape must trip RL001 AND RL006."""
    found = lint("""
        import threading
        import time
        import numpy as np

        class Gateway:
            def run_until_drained(self, rec, engine):
                t0 = time.perf_counter()
                while not engine.idle:
                    # busy-spin: device sync per poll, all of it timed
                    toks = np.asarray(engine.pool.tokens)
                    self.emit(toks)
                rec.add("response", time.perf_counter() - t0)

            def _watchdog(self):
                while True:
                    try:
                        self.poke()
                    except:
                        pass

            def start(self):
                t = threading.Thread(target=self._watchdog, daemon=True)
                t.start()
    """, filename="src/repro/serving/cluster.py")
    assert "RL001" in codes(found), found
    assert "RL006" in codes(found), found


# --------------------------------------------------------------------------- #
# RL007 trace coverage
# --------------------------------------------------------------------------- #
UNTRACED_STAGE = """
    import time

    def _prefill_bucket(self, rec, toks):
        t0 = time.perf_counter()
        rec.add("inference", time.perf_counter() - t0)
"""


def test_rl007_flags_untraced_stage_charge():
    found = lint(UNTRACED_STAGE)
    assert codes(found) == ["RL007"]
    assert "emits no span" in found[0].message


def test_rl007_silent_with_emit_or_trace_helper():
    # direct trace.tracer().emit(...)
    assert lint("""
        import time
        from repro.core import trace

        def _prefill_bucket(self, rec, toks):
            t0 = time.perf_counter()
            rec.add("inference", time.perf_counter() - t0)
            trace.tracer().emit("prefill.bucket", t0, time.perf_counter())
    """) == []
    # indirect: a _trace* helper carries the emit
    assert lint("""
        import time

        def _finish(self, rec, entry):
            t0 = time.perf_counter()
            rec.add("inference", time.perf_counter() - t0)
            self._trace_admission(entry)
    """) == []
    # a trace.span(...) context: the span is emitted on exit
    assert lint("""
        import time
        from repro.core import trace

        def _harvest(self, rec, entry):
            with trace.span("engine.harvest", live=1):
                t0 = time.perf_counter()
                rec.add("inference", time.perf_counter() - t0)
    """) == []


def test_rl007_drain_hook_is_not_a_span():
    # a drain hook is no span emitter: the charge stays untraced
    found = lint("""
        import time

        def _finish(self, rec, entry):
            t0 = time.perf_counter()
            rec.add("inference", time.perf_counter() - t0)
            self.trace_flush()
    """)
    assert codes(found) == ["RL007"]


def test_rl007_scoped_to_hot_files_and_untimed_functions():
    # same shape outside the hot files: out of scope
    assert lint(UNTRACED_STAGE, filename="src/repro/serving/loadgen.py") == []
    # charges a stage but never reads the clock (modeled cost): not a
    # timed-stage function, so no span is demanded
    assert lint("""
        def submit(self, rec, hop):
            rec.add("request", hop)
    """) == []


# --------------------------------------------------------------------------- #
# suppressions, baselines, CLI, shipped tree
# --------------------------------------------------------------------------- #
def test_suppression_requires_justification():
    # justified: silent.  bare: the suppression itself is reported (RL000)
    assert lint("""
        import time
        import numpy as np

        def _prefill(self, rec, toks):
            t0 = time.perf_counter()
            host = np.asarray(toks)  # reprolint: disable=RL001 deliberate timing fence
            rec.add("preprocess", time.perf_counter() - t0)
            self._trace_admission(rec, t0)
            return host
    """) == []
    found = lint("""
        import time
        import numpy as np

        def _prefill(self, rec, toks):
            t0 = time.perf_counter()
            host = np.asarray(toks)  # reprolint: disable=RL001
            rec.add("preprocess", time.perf_counter() - t0)
            self._trace_admission(rec, t0)
            return host
    """)
    assert codes(found) == ["RL000"]


def test_def_line_suppression_covers_whole_function():
    assert lint("""
        import time
        import numpy as np

        def _step_legacy(self, rec):  # reprolint: disable=RL001,RL007 legacy baseline blocks and is trace-exempt by design
            t0 = time.perf_counter()
            a = np.asarray(self.tokens)
            b = self.logits.item()
            rec.add("inference", time.perf_counter() - t0)
            return a, b
    """) == []


def test_syntax_error_becomes_finding_not_crash(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    found = lint_paths([bad])
    assert codes(found) == ["RL000"]
    assert "does not parse" in found[0].message


def test_baseline_fingerprints_survive_line_moves(tmp_path):
    src = textwrap.dedent(TIMED_SYNC)
    f = tmp_path / "engine.py"
    f.write_text(src)
    mod_path = "src/repro/serving/engine.py"
    first = lint_source(src, filename=mod_path)
    # grandfather it, then shift every line down: same fingerprint
    base = tmp_path / "baseline.json"
    save_baseline(base, first)
    shifted = lint_source("# header comment\n\n" + src, filename=mod_path)
    assert [x.fingerprint for x in shifted] == \
        [x.fingerprint for x in first]
    assert {x.fingerprint for x in shifted} <= load_baseline(base)


def test_cli_strict_clean_on_shipped_tree_and_lists_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.reprolint", "--strict"],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout
    proc = subprocess.run(
        [sys.executable, "-m", "tools.reprolint", "--list-rules"],
        capture_output=True, text=True, cwd=ROOT, timeout=60,
    )
    assert proc.returncode == 0
    for code in ("RL001", "RL002", "RL003", "RL004", "RL005", "RL006",
                 "RL007"):
        assert code in proc.stdout


def test_unified_checks_entry_point_runs_all():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.checks"],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for tag in ("[docs]", "[bench]", "[lint]"):
        assert f"{tag} ok" in proc.stdout
    # unknown checker name -> usage error, not a silent pass
    proc = subprocess.run(
        [sys.executable, "-m", "tools.checks", "--only", "nope"],
        capture_output=True, text=True, cwd=ROOT, timeout=60,
    )
    assert proc.returncode == 2


def test_every_rule_is_registered_and_documented():
    have = {r.code for r in RULES}
    assert have == {"RL001", "RL002", "RL003", "RL004", "RL005", "RL006",
                    "RL007"}
    lint_md = (ROOT / "docs" / "lint.md").read_text()
    for code in sorted(have):
        assert code in lint_md, f"docs/lint.md must document {code}"
