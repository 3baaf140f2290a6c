"""graftlint tests (ISSUE 4): every rule fires on its bad exemplar and
stays silent on the good twin; suppressions, the baseline ledger, the CLI
contract, and — the acceptance bar — the repo at HEAD lints clean with
the `multilayer.py:392` score sync FIXED, not baselined.

Fixture snippets are inline source strings through ``lint_source`` (no
jax import needed by the analyzer; the snippets never execute)."""

import json
import textwrap
from pathlib import Path

import pytest

from deeplearning4j_tpu import analysis
from deeplearning4j_tpu.analysis import (apply_baseline, lint_paths,
                                         lint_source, load_baseline,
                                         save_baseline)
from deeplearning4j_tpu.cli import main

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "deeplearning4j_tpu"


def rules_fired(src, rules=None):
    findings, err = lint_source(textwrap.dedent(src), rules=rules)
    assert err is None, err
    return findings


def rule_set(src, rules=None):
    return {f.rule for f in rules_fired(src, rules)}


# ----------------------------------------------------------------------
# R1: hidden host syncs
# ----------------------------------------------------------------------

class TestR1HostSync:
    BAD_TRACED = """
        import jax

        def make_train_step(net):
            def train_step(params, x, y):
                loss, grads = net.grad(params, x, y)
                log_val = float(loss)  # tracer leak
                return params, loss
            return jax.jit(train_step)
    """

    GOOD_TRACED = """
        import jax
        import jax.numpy as jnp

        def make_train_step(net):
            def train_step(params, x, y):
                loss, grads = net.grad(params, x, y)
                loss32 = jnp.asarray(loss, jnp.float32)  # stays on device
                return params, loss32
            return jax.jit(train_step)
    """

    def test_traced_float_fires(self):
        fs = [f for f in rules_fired(self.BAD_TRACED) if f.rule == "R1"]
        assert len(fs) == 1
        assert "float" in fs[0].message
        assert fs[0].line == 7

    def test_traced_good_twin_silent(self):
        assert "R1" not in rule_set(self.GOOD_TRACED)

    BAD_LOOP = """
        def fit(self, batches):
            for x, y in batches:
                loss = self._train_step(x, y)
                score = float(loss)  # one sync per iteration
                self.scores.append(score)
    """

    GOOD_LOOP = """
        def fit(self, batches):
            total = 0.0
            for x, y in batches:
                loss = self._train_step(x, y)
                total = total + loss  # device accumulate
            return float(total)  # ONE sync, after the loop
    """

    def test_steploop_per_iteration_sync_fires(self):
        fs = [f for f in rules_fired(self.BAD_LOOP) if f.rule == "R1"]
        assert len(fs) == 1
        assert "per-iteration" in fs[0].message

    def test_steploop_device_accumulate_silent(self):
        assert "R1" not in rule_set(self.GOOD_LOOP)

    def test_untainted_host_conversion_in_loop_silent(self):
        # np.asarray on HOST input data is free — only step results count
        src = """
            import numpy as np

            def fit(self, data, batches):
                for i in batches:
                    x = np.asarray(data[i])
                    loss = self._train_step(x)
        """
        assert "R1" not in rule_set(src)

    def test_one_shot_score_api_silent(self):
        # a single float() outside any loop is the score() contract
        src = """
            def score(self, x, y):
                loss = self.loss_fn(x, y)
                return float(loss)
        """
        assert "R1" not in rule_set(src)

    def test_device_get_and_item_variants_fire(self):
        src = """
            import jax

            def fit(self, batches):
                for x in batches:
                    loss = self.step_fn(x)
                    a = jax.device_get(loss)
                    b = loss.item()
        """
        fs = [f for f in rules_fired(src) if f.rule == "R1"]
        assert len(fs) == 2

    def test_static_shape_int_in_traced_silent(self):
        src = """
            import jax
            import numpy as np

            @jax.jit
            def fwd(x):
                n = int(x.shape[0])
                m = int(np.prod(x.shape[1:]))
                return x.reshape((n, m))
        """
        assert "R1" not in rule_set(src)


# ----------------------------------------------------------------------
# R2: control flow on traced values
# ----------------------------------------------------------------------

class TestR2TracedBranch:
    def test_comparison_branch_fires(self):
        src = """
            import jax

            @jax.jit
            def step(params, loss):
                if loss > 100.0:
                    return params
                return params
        """
        fs = [f for f in rules_fired(src) if f.rule == "R2"]
        assert len(fs) == 1

    def test_jnp_predicate_branch_fires(self):
        src = """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def step(params, grads):
                if jnp.any(jnp.isnan(grads)):
                    return params
                return params
        """
        assert "R2" in rule_set(src)

    def test_static_idioms_silent(self):
        src = """
            import jax

            @jax.jit
            def step(params, x, mask=None):
                if mask is not None:       # sentinel: static
                    x = x * mask
                if x.ndim == 3:            # shape metadata: static
                    x = x.reshape((x.shape[0], -1))
                if params:                 # pytree structure: static
                    x = x + 1
                return x
        """
        assert "R2" not in rule_set(src)

    def test_host_function_branches_silent(self):
        src = """
            def fit(self, loss):
                if loss > 100.0:
                    return None
        """
        assert "R2" not in rule_set(src)


# ----------------------------------------------------------------------
# R3: recompile hazards
# ----------------------------------------------------------------------

class TestR3Recompile:
    def test_jit_in_loop_fires(self):
        src = """
            import jax

            def serve(self, reqs):
                for r in reqs:
                    f = jax.jit(self.forward)
                    f(r)
        """
        fs = [f for f in rules_fired(src) if f.rule == "R3"]
        assert len(fs) == 1
        assert "loop" in fs[0].message

    def test_jit_lambda_per_call_fires(self):
        src = """
            import jax

            def featurize(self, x):
                return jax.jit(lambda p: p * 2)(x)
        """
        assert "R3" in rule_set(src)

    def test_cached_maker_silent(self):
        src = """
            import jax

            def make_train_step(self):
                def train_step(params, x):
                    return params
                return jax.jit(train_step)

            def fit(self, batches):
                if self._step is None:
                    self._step = self.make_train_step()
                for x in batches:
                    self._step(x)
        """
        assert "R3" not in rule_set(src)

    def test_module_level_jit_lambda_silent(self):
        assert "R3" not in rule_set("""
            import jax
            double = jax.jit(lambda x: x * 2)
        """)

    def test_trace_time_checkpoint_loop_silent(self):
        # per-layer jax.checkpoint inside a traced forward unrolls ONCE
        # at trace time — the remat idiom, not a recompile storm
        src = """
            import jax

            @jax.jit
            def fwd(params, x):
                for p in params:
                    run = jax.checkpoint(lambda q, xx: xx @ q)
                    x = run(p, x)
                return x
        """
        assert "R3" not in rule_set(src)

    def test_raw_lower_compile_chain_fires(self):
        # ISSUE 9: an AOT compile outside utils/compile_cache.aot_compile
        # can never be served from a warm manifest — every restart pays it
        src = """
            import jax

            def warmup(self, spec):
                ex = jax.jit(self.fwd).lower(spec).compile()
                return ex
        """
        fs = [f for f in rules_fired(src) if f.rule == "R3"]
        assert len(fs) == 1
        assert "compile-artifact cache" in fs[0].message

    def test_lower_compile_in_cache_tier_silent(self):
        # the blessed site itself: utils/compile_cache.aot_compile
        src = textwrap.dedent("""
            def aot_compile(jitted, *args):
                return jitted.lower(*args).compile()
        """)
        from deeplearning4j_tpu.analysis import core
        mod = core.LintModule(src, path="utils/compile_cache.py")
        fired = {f.rule for f in analysis.lint_modules([mod])}
        assert "R3" not in fired

    def test_split_lower_compile_silent(self):
        # bench.py idiom: lowered kept for cost_analysis, compiled
        # separately — a deliberate one-shot, not a chained bypass
        src = """
            import jax

            def measure(self, step, args):
                lowered = jax.jit(step).lower(*args)
                hlo = lowered.as_text()
                compiled = lowered.compile()
                return hlo, compiled
        """
        assert "R3" not in rule_set(src)

    def test_jit_in_loop_into_aot_compile_silent(self):
        # ISSUE 11: a search that deliberately compiles one candidate per
        # loop iteration — routed through the blessed manifest-aware site,
        # that is the search working, not a recompile hazard (the harness
        # that did so went with the autotuner in PR 29; ROADMAP D17)
        src = """
            import jax
            from deeplearning4j_tpu.utils.compile_cache import aot_compile

            def search(self, candidates, args):
                best = None
                for cand in candidates:
                    jitted = jax.jit(self.build(cand))
                    ex, _src = aot_compile(jitted, *args)
                    best = self.keep_best(best, ex, args)
                return best
        """
        assert "R3" not in rule_set(src)

    def test_jit_in_loop_into_aot_compile_direct_arg_silent(self):
        # direct-argument form, via the module-alias spelling
        src = """
            import jax
            from deeplearning4j_tpu.utils import compile_cache as _cc

            def search(self, candidates, args):
                for cand in candidates:
                    ex, _src = _cc.aot_compile(jax.jit(self.build(cand)),
                                               *args)
                    self.note(ex)
        """
        assert "R3" not in rule_set(src)

    def test_jit_in_loop_without_aot_compile_still_fires(self):
        # the bad twin: same loop shape, but the compile bypasses the
        # cache tier — every iteration is an untracked recompile
        src = """
            import jax

            def search(self, candidates, args):
                for cand in candidates:
                    jitted = jax.jit(self.build(cand))
                    jitted(*args)
        """
        fs = [f for f in rules_fired(src) if f.rule == "R3"]
        assert len(fs) == 1
        assert "aot_compile" in fs[0].message


# ----------------------------------------------------------------------
# R4: impure jit bodies
# ----------------------------------------------------------------------

class TestR4ImpureJit:
    def test_clock_in_traced_fires(self):
        src = """
            import jax
            import time

            @jax.jit
            def step(params):
                t0 = time.perf_counter()
                return params
        """
        fs = [f for f in rules_fired(src) if f.rule == "R4"]
        assert len(fs) == 1

    def test_telemetry_record_in_traced_fires(self):
        src = """
            import jax
            from deeplearning4j_tpu import telemetry as _tm

            @jax.jit
            def step(params, loss):
                _tm.get_registry()
                return params
        """
        assert "R4" in rule_set(src)

    def test_numpy_rng_in_traced_fires(self):
        src = """
            import jax
            import numpy as np

            @jax.jit
            def step(params):
                noise = np.random.randn(4)
                return params
        """
        assert "R4" in rule_set(src)

    def test_pure_health_bundle_silent(self):
        # the sanctioned fused-stats entry points are pure jnp math
        src = """
            import jax
            from deeplearning4j_tpu.telemetry import health as _health

            @jax.jit
            def step(params, grads, loss):
                hb = _health.health_stats(grads, params, loss)
                return params, hb
        """
        assert "R4" not in rule_set(src)

    def test_host_loop_telemetry_silent(self):
        src = """
            import time
            from deeplearning4j_tpu import telemetry as _tm

            def fit(self):
                t0 = time.perf_counter()
                _tm.get_registry()
        """
        assert "R4" not in rule_set(src)

    def test_tracectx_in_traced_fires_with_tailored_message(self):
        # a contextvar read inside traced code fires at trace time only —
        # R4 knows tracectx specifically and says where it belongs
        src = """
            import jax
            from deeplearning4j_tpu.telemetry import tracectx as _tracectx

            @jax.jit
            def step(params):
                ctx = _tracectx.current()
                return params
        """
        fs = [f for f in rules_fired(src) if f.rule == "R4"]
        assert len(fs) == 1
        assert "trace-context" in fs[0].message
        assert "attach/handoff" in fs[0].message

    def test_tracectx_listener_path_silent(self):
        # tracectx reads are telemetry-gated host bookkeeping — the
        # listener/drain/producer paths use them freely
        src = """
            from deeplearning4j_tpu.telemetry import tracectx as _tracectx

            def iteration_done(self, net, it):
                ctx = _tracectx.maybe_start("step", it=it)
                with _tracectx.attach(ctx):
                    pass
        """
        assert "R4" not in rule_set(src)


# ----------------------------------------------------------------------
# R5: unguarded backend-specific calls
# ----------------------------------------------------------------------

class TestR5BackendGuard:
    def test_unguarded_memory_stats_fires(self):
        src = """
            import jax

            def poll():
                return jax.devices()[0].memory_stats()
        """
        fs = [f for f in rules_fired(src) if f.rule == "R5"]
        assert len(fs) == 1

    def test_guarded_silent(self):
        src = """
            import jax

            def poll():
                try:
                    return jax.devices()[0].memory_stats()
                except Exception:
                    return None
        """
        assert "R5" not in rule_set(src)


# ----------------------------------------------------------------------
# R6: concurrency smells
# ----------------------------------------------------------------------

class TestR6ThreadDiscipline:
    def test_thread_without_daemon_fires(self):
        src = """
            import threading

            def start(fn):
                t = threading.Thread(target=fn)
                t.start()
        """
        fs = [f for f in rules_fired(src) if f.rule == "R6"]
        assert len(fs) == 1
        assert "daemon" in fs[0].message

    def test_thread_with_daemon_silent(self):
        assert "R6" not in rule_set("""
            import threading

            def start(fn):
                threading.Thread(target=fn, daemon=True).start()
        """)

    LOCKED_CLASS = """
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []
                self.count = 0

            def add_unlocked(self, x):
                self._items.append(x)
                self.count += 1

            def add_locked(self, x):
                with self._lock:
                    self._items.append(x)
                    self.count += 1
    """

    def test_unlocked_rmw_fires_locked_silent(self):
        fs = [f for f in rules_fired(self.LOCKED_CLASS) if f.rule == "R6"]
        assert len(fs) == 2  # append + augassign in add_unlocked only
        assert all(f.line in (11, 12) for f in fs)

    def test_lockless_class_silent(self):
        # no lock attr -> no ownership contract to enforce
        assert "R6" not in rule_set("""
            import threading

            class Bag:
                def __init__(self):
                    self._items = []

                def add(self, x):
                    self._items.append(x)
        """)

    def test_init_writes_silent(self):
        assert "R6" not in rule_set("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []
                    self._items.append(1)  # single-threaded construction
        """)


# ----------------------------------------------------------------------
# suppressions
# ----------------------------------------------------------------------

class TestSuppressions:
    def test_line_suppression(self):
        src = """
            def fit(self, batches):
                for x in batches:
                    loss = self.step_fn(x)
                    s = float(loss)  # graftlint: disable=R1 -- deliberate
        """
        assert "R1" not in rule_set(src)

    def test_line_suppression_is_rule_specific(self):
        src = """
            def fit(self, batches):
                for x in batches:
                    loss = self.step_fn(x)
                    s = float(loss)  # graftlint: disable=R2
        """
        assert "R1" in rule_set(src)

    def test_disable_all(self):
        src = """
            def fit(self, batches):
                for x in batches:
                    loss = self.step_fn(x)
                    s = float(loss)  # graftlint: disable=all
        """
        assert rules_fired(src) == []

    def test_comma_in_justification_does_not_widen_suppression(self):
        # a comma inside the "-- reason" tail must not smuggle extra
        # rule names into the suppressed set
        src = """
            import jax

            @jax.jit
            def step(params, loss):
                import time
                t0 = time.perf_counter()
                s = float(loss)  # graftlint: disable=R1 -- overlaps collective, R4 pattern not applicable
                return params
        """
        fired = rule_set(src)
        assert "R1" not in fired      # named: suppressed
        assert "R4" in fired          # only mentioned in prose: still fires

    def test_multiline_statement_suppressed_from_closing_line(self):
        src = """
            def fit(self, batches):
                for b in batches:
                    loss = self.step_fn(b)
                    s = float(
                        loss)  # graftlint: disable=R1 -- trailing-line style
        """
        assert "R1" not in rule_set(src)

    def test_file_level_suppression(self):
        src = """
            # graftlint: disable-file=R1
            def fit(self, batches):
                for x in batches:
                    loss = self.step_fn(x)
                    s = float(loss)
                    t = loss.item()
        """
        assert "R1" not in rule_set(src)


# ----------------------------------------------------------------------
# baseline mechanism
# ----------------------------------------------------------------------

class TestBaseline:
    SRC = """
        def fit(self, batches):
            for x in batches:
                loss = self.step_fn(x)
                s = float(loss)
    """

    def test_roundtrip_absorbs_and_detects_new_and_stale(self, tmp_path):
        findings = rules_fired(self.SRC)
        assert findings
        bpath = tmp_path / "baseline.json"
        save_baseline(bpath, findings)
        baseline = load_baseline(bpath)

        # identical run: everything absorbed
        new, known, stale = apply_baseline(findings, baseline)
        assert new == [] and len(known) == len(findings) and stale == {}

        # a new violation is NOT absorbed
        worse = rules_fired(self.SRC.replace(
            "s = float(loss)",
            "s = float(loss)\n                t = loss.item()"))
        new, known, stale = apply_baseline(worse, baseline)
        assert len(new) == 1 and ".item()" in new[0].message

        # fixing the violation leaves a stale ledger entry
        new, known, stale = apply_baseline([], baseline)
        assert new == [] and known == [] and len(stale) == 1

    def test_missing_baseline_is_empty(self, tmp_path):
        assert load_baseline(tmp_path / "absent.json") == {}

    def test_key_survives_line_drift(self):
        a = rules_fired(self.SRC)[0]
        b = rules_fired("\n\n\n" + textwrap.dedent(self.SRC))[0]
        assert a.line != b.line
        assert a.key() == b.key()


# ----------------------------------------------------------------------
# CLI contract (the ISSUE 4 acceptance shape)
# ----------------------------------------------------------------------

class TestLintCli:
    BAD = textwrap.dedent("""
        import jax

        def make_train_step(net):
            def train_step(params, x, y):
                loss = net.loss(params, x, y)
                score = float(loss)
                return params, loss
            return jax.jit(train_step)
    """)

    def test_exits_nonzero_on_traced_float_fixture(self, tmp_path, capsys):
        # acceptance: float() on a traced value inside a jitted step fn
        p = tmp_path / "bad.py"
        p.write_text(self.BAD)
        rc = main(["lint", str(p), "--no-baseline"])
        assert rc == 1
        assert "R1[host-sync]" in capsys.readouterr().err

    def test_exits_zero_on_clean_file(self, tmp_path):
        p = tmp_path / "ok.py"
        p.write_text("def f():\n    return 1\n")
        assert main(["lint", str(p), "--no-baseline"]) == 0

    def test_rule_selection(self, tmp_path):
        p = tmp_path / "bad.py"
        p.write_text(self.BAD)
        assert main(["lint", str(p), "--no-baseline", "--rules", "R5"]) == 0
        assert main(["lint", str(p), "--no-baseline", "--rules", "R1"]) == 1

    def test_unknown_rule_is_usage_error(self, tmp_path):
        p = tmp_path / "ok.py"
        p.write_text("x = 1\n")
        with pytest.raises(SystemExit):
            main(["lint", str(p), "--no-baseline", "--rules", "R99"])

    def test_json_format(self, tmp_path, capsys):
        p = tmp_path / "bad.py"
        p.write_text(self.BAD)
        rc = main(["lint", str(p), "--no-baseline", "--format", "json"])
        assert rc == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["counts"]["new"] == 1
        assert doc["new"][0]["rule"] == "R1"

    def test_list_rules(self, capsys):
        assert main(["lint", "--list-rules"]) == 0
        out = capsys.readouterr().out
        for r in ("R1", "R2", "R3", "R4", "R5", "R6"):
            assert r in out

    def test_update_then_strict_gate(self, tmp_path, capsys):
        p = tmp_path / "bad.py"
        p.write_text(self.BAD)
        b = tmp_path / "base.json"
        assert main(["lint", str(p), "--baseline", str(b),
                     "--update-baseline"]) == 0
        # baselined: gate passes
        assert main(["lint", str(p), "--baseline", str(b)]) == 0
        # debt fixed but ledger not updated: strict mode fails, lax passes
        p.write_text("def f():\n    return 1\n")
        assert main(["lint", str(p), "--baseline", str(b)]) == 0
        assert main(["lint", str(p), "--baseline", str(b),
                     "--strict-baseline"]) == 1

    def test_parse_error_reported_not_fatal(self, tmp_path, capsys):
        p = tmp_path / "broken.py"
        p.write_text("def f(:\n")
        rc = main(["lint", str(p), "--no-baseline"])
        assert rc == 1
        assert "parse-error" in capsys.readouterr().err


# ----------------------------------------------------------------------
# the repo itself (acceptance: HEAD lints clean; multilayer FIXED)
# ----------------------------------------------------------------------

class TestRepoIsClean:
    def test_package_lints_clean_against_committed_baseline(self):
        findings = lint_paths([PKG], root=REPO)
        baseline = load_baseline(REPO / "graftlint.baseline.json")
        new, _known, stale = apply_baseline(findings, baseline)
        assert new == [], "\n".join(f.human() for f in new)
        assert stale == {}, f"stale baseline entries: {sorted(stale)}"

    def test_multilayer_score_sync_fixed_not_baselined(self):
        # ISSUE 4 satellite: the per-iteration float(loss) score sync in
        # the MLN fit loop is GONE — no R1 finding, no suppression, no
        # baseline entry for nn/multilayer.py
        findings = lint_paths([PKG / "nn" / "multilayer.py"], root=REPO)
        assert [f for f in findings if f.rule == "R1"] == []
        baseline = load_baseline(REPO / "graftlint.baseline.json")
        assert not any("nn/multilayer.py" in k and k.startswith("R1")
                       for k in baseline)
        src = (PKG / "nn" / "multilayer.py").read_text()
        assert "graftlint: disable=R1" not in src

    def test_swept_modules_have_empty_baseline(self):
        # ISSUE 4 satellite: graph.py / distributed.py / health.py carry
        # zero baseline debt for the step-path rules
        baseline = load_baseline(REPO / "graftlint.baseline.json")
        for mod in ("nn/graph.py", "parallel/distributed.py",
                    "telemetry/health.py"):
            assert not any(mod in k for k in baseline), mod

    def test_dataflow_rules_clean_at_head_with_empty_baseline(self):
        # ISSUE 7 acceptance: R7/R8/R9 surface nothing at HEAD (findings
        # were FIXED, not baselined) and the ledger holds zero entries
        findings = lint_paths([PKG], root=REPO, rules=["R7", "R8", "R9"])
        assert findings == [], "\n".join(f.human() for f in findings)
        baseline = load_baseline(REPO / "graftlint.baseline.json")
        assert baseline == {}

    def test_serving_engine_reads_params_live_not_snapshotted(self):
        # the PR 6 incident fix stays fixed: no R7 finding and no
        # suppression in the serving engine
        findings = lint_paths([PKG / "serving" / "engine.py"], root=REPO)
        assert [f for f in findings if f.rule == "R7"] == []
        src = (PKG / "serving" / "engine.py").read_text()
        assert "graftlint: disable=R7" not in src

    def test_analysis_package_needs_no_jax(self):
        # the linter must run in environments without an accelerator
        # stack: its modules import only stdlib
        import ast as ast_mod
        for f in (PKG / "analysis").glob("*.py"):
            tree = ast_mod.parse(f.read_text())
            for node in ast_mod.walk(tree):
                names = []
                if isinstance(node, ast_mod.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast_mod.ImportFrom) and node.module:
                    names = [node.module]
                for n in names:
                    assert not n.startswith(("jax", "numpy")), (f, n)


# ----------------------------------------------------------------------
# ScorePipeline (the R1 remediation helper the fit loops now use)
# ----------------------------------------------------------------------

class TestScorePipeline:
    def test_one_step_late_ordering(self):
        from deeplearning4j_tpu.telemetry.scorepipe import ScorePipeline

        pipe = ScorePipeline()
        assert pipe.push(1.5, {"step": 0}) is None
        assert pipe.pending
        score, meta = pipe.push(2.5, {"step": 1})
        assert score == 1.5 and meta == {"step": 0}
        score, meta = pipe.flush()
        assert score == 2.5 and meta == {"step": 1}
        assert pipe.flush() is None
        assert not pipe.pending

    def test_resolves_device_scalars(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.telemetry.scorepipe import ScorePipeline

        pipe = ScorePipeline()
        pipe.push(jnp.float32(3.25), None)
        score, _ = pipe.flush()
        assert score == 3.25

    def test_fit_loop_listener_scores_match_per_step_losses(self):
        # integration: the pipelined fit still hands every listener one
        # callback per iteration, in order, with that step's own score
        import numpy as np

        from deeplearning4j_tpu.nn import layers as L, updaters as U
        from deeplearning4j_tpu.nn.conf import inputs as I
        from deeplearning4j_tpu.nn.conf.network import NeuralNetConfig
        from deeplearning4j_tpu.nn.listeners import ScoreIterationListener
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        rs = np.random.RandomState(0)
        x = rs.randn(64, 4).astype(np.float32)
        y = np.eye(2)[rs.randint(0, 2, 64)].astype(np.float32)
        net = MultiLayerNetwork(
            NeuralNetConfig(seed=7, updater=U.Sgd(0.1)).list(
                L.DenseLayer(n_out=8, activation="relu"),
                L.OutputLayer(n_out=2, loss="mcxent"),
                input_type=I.FeedForwardType(4)))
        lst = ScoreIterationListener(frequency=1000,
                                     print_fn=lambda s: None)
        net.add_listener(lst)
        net.fit(x, y, epochs=2, batch_size=16)
        assert len(lst.scores) == 8  # 4 batches x 2 epochs, none lost
        iterations = [it for it, _ in lst.scores]
        assert iterations == sorted(iterations)
        assert all(np.isfinite(s) for _, s in lst.scores)


# ----------------------------------------------------------------------
# R7: use-after-donate (ISSUE 7 — the PR 6 serving-snapshot crash class)
# ----------------------------------------------------------------------

MAKER = """
    import jax

    def make_step():
        def step(params, x):
            return params
        return jax.jit(step, donate_argnums=(0,))
"""


class TestR7UseAfterDonate:
    # the PR 6 incident shape: a params snapshot taken at engine
    # construction (BEFORE the donating fit) read again at serve time —
    # the buffer belongs to XLA by then
    BAD_SNAPSHOT = MAKER + """
    class Server:
        def fit_then_serve(self, x):
            snap = self.net.params        # construction-time snapshot
            step = make_step()
            self.net.params = step(self.net.params, x)
            return snap                   # stale alias: PR 6 crash
    """

    GOOD_LIVE_READ = MAKER + """
    class Server:
        def fit_then_serve(self, x):
            step = make_step()
            self.net.params = step(self.net.params, x)
            return self.net.params        # live read: rebound from results
    """

    def test_pr6_snapshot_fixture_fires(self):
        fs = [f for f in rules_fired(self.BAD_SNAPSHOT) if f.rule == "R7"]
        assert len(fs) == 1
        assert "alias" in fs[0].message
        assert "snap" in fs[0].message

    def test_pr6_fixed_idiom_silent(self):
        assert "R7" not in rule_set(self.GOOD_LIVE_READ)

    BAD_LOOP = MAKER + """
    def fit(net, batches):
        step = make_step()
        params = net.params
        for x in batches:
            step(params, x)               # donated, never rebound
    """

    GOOD_LOOP = MAKER + """
    def fit(net, batches):
        step = make_step()
        params = net.params
        for x in batches:
            params = step(params, x)      # rebound each iteration
        return params
    """

    def test_fused_scan_loop_hazard_fires(self):
        fs = [f for f in rules_fired(self.BAD_LOOP) if f.rule == "R7"]
        assert len(fs) == 1
        assert "next iteration" in fs[0].message

    def test_rebinding_loop_silent(self):
        assert "R7" not in rule_set(self.GOOD_LOOP)

    def test_direct_read_after_donating_call_fires(self):
        src = MAKER + """
    def score(net, x):
        step = make_step()
        params = net.params
        out = step(params, x)
        return params.mean()              # read of the donated binding
    """
        fs = [f for f in rules_fired(src) if f.rule == "R7"]
        assert len(fs) == 1
        assert "donated" in fs[0].message

    def test_interprocedural_summary_fires_in_caller(self):
        # train_k donates its params PARAMETER; the caller's read after
        # calling train_k is the finding — the seam R1-R6 cannot see
        src = MAKER + """
    def train_k(params, x):
        step = make_step()
        return step(params, x)

    def fit(net, x):
        params = net.params
        out = train_k(params, x)
        return params.block_until_ready()
    """
        fs = [f for f in rules_fired(src) if f.rule == "R7"]
        assert [f.line for f in fs] and all(f.rule == "R7" for f in fs)

    def test_cross_module_maker_fires(self):
        # the donating jit lives two modules away from the reading loop
        mod_a = textwrap.dedent(MAKER)
        mod_b = textwrap.dedent("""
            from pkg.a import make_step

            def fit(net, batches):
                step = make_step()
                params = net.params
                for x in batches:
                    step(params, x)
        """)
        mods = [analysis.LintModule(mod_a, path="pkg/a.py"),
                analysis.LintModule(mod_b, path="pkg/b.py")]
        fs = [f for f in analysis.lint_modules(mods, rules=["R7"])]
        assert len(fs) == 1 and fs[0].path == "pkg/b.py"

    def test_branch_arms_are_not_a_path(self):
        # the read in the OTHER arm of the same If is not reachable
        # after the donating call — must stay silent
        src = MAKER + """
    def fit(net, x, donate):
        step = make_step()
        params = net.params
        if donate:
            step(params, x)
        else:
            return params.mean()
    """
        assert "R7" not in rule_set(src)


# ----------------------------------------------------------------------
# R8: sharding / collective discipline
# ----------------------------------------------------------------------

class TestR8ShardingDiscipline:
    def test_unmapped_collective_fires(self):
        src = """
            import jax

            def rollup(x):
                return jax.lax.psum(x, "data")
        """
        fs = [f for f in rules_fired(src) if f.rule == "R8"]
        assert len(fs) == 1
        assert "no shard_map/pmap" in fs[0].message

    GOOD_MAPPED = """
        import jax
        from functools import partial
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(None, axis_names=("data",))

        @partial(shard_map, mesh=mesh, in_specs=(P("data"),),
                 out_specs=P("data"))
        def rollup(x):
            return jax.lax.psum(x, "data")
    """

    def test_mapped_matching_axis_silent(self):
        assert "R8" not in rule_set(self.GOOD_MAPPED)

    def test_axis_not_bound_by_context_fires(self):
        src = self.GOOD_MAPPED.replace('jax.lax.psum(x, "data")',
                                       'jax.lax.psum(x, "model")')
        fs = [f for f in rules_fired(src) if f.rule == "R8"]
        assert len(fs) == 1
        assert "not bound" in fs[0].message

    def test_spec_axis_absent_from_mesh_fires(self):
        src = self.GOOD_MAPPED.replace('in_specs=(P("data"),)',
                                       'in_specs=(P("model"),)')
        fs = [f for f in rules_fired(src) if f.rule == "R8"]
        assert any("spec axis 'model'" in f.message for f in fs)

    def test_escaped_callable_checked_against_universe_only(self):
        # grad_sync escapes as a value: SOME mapped context may call it,
        # so "outside mapped context" must not fire — but an axis name
        # no Mesh in the project declares is still a finding
        src = """
            import jax
            from jax.sharding import Mesh

            mesh = Mesh(None, axis_names=("data",))

            def grad_sync(g):
                return jax.lax.pmean(g, "dat")

            def run(fn):
                return fn

            handle = run(grad_sync)
        """
        fs = [f for f in rules_fired(src) if f.rule == "R8"]
        assert len(fs) == 1
        assert "matches no" in fs[0].message
        assert "R8" not in rule_set(src.replace('"dat"', '"data"'))

    # -- the streamed-gather / stage-axis idiom (ISSUE 14): a collective
    # with a scan-carried block index runs in the context of the function
    # that CALLS lax.scan, so the body must sit under a mapped context
    # whose mesh binds the axis — precise axes now propagate through the
    # jax higher-order combinators instead of the body escaping with
    # unknown axes

    SCAN_BODY = """
        import jax
        import numpy as np
        from jax import lax
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()),
                    axis_names=("data", "stage"))

        def _body(h, bp):
            nxt = lax.ppermute(h, "stage", [(0, 1)])
            return nxt, None

        def run(slab, x):
            h, _ = lax.scan(_body, x, slab)
            return h
    """

    def test_scan_body_collective_unmapped_fires(self):
        # the body is ONLY ever scanned from an unmapped function: the
        # old escaped-with-unknown-axes bailout stayed silent here
        fs = [f for f in rules_fired(self.SCAN_BODY) if f.rule == "R8"]
        assert len(fs) == 1
        assert "no shard_map/pmap" in fs[0].message

    def test_scan_body_under_mapped_context_silent(self):
        # same body, but the scanning function is shard_map'd over a
        # mesh that binds 'stage' — the streamed-gather idiom, clean
        src = self.SCAN_BODY + """
        piped = shard_map(run, mesh=mesh, in_specs=(P("stage"), P()),
                          out_specs=P())
        """
        assert "R8" not in rule_set(src)

    def test_scan_body_axis_not_on_mesh_fires(self):
        # mapped, but the mesh does NOT bind 'stage': the body's
        # ppermute inherits the caller's precise axes and is flagged
        src = self.SCAN_BODY.replace(
            'axis_names=("data", "stage")', 'axis_names=("data",)')
        src = src + """
        piped = shard_map(run, mesh=mesh, in_specs=(P("data"), P()),
                          out_specs=P())
        """
        fs = [f for f in rules_fired(src) if f.rule == "R8"]
        assert len(fs) == 1
        assert "not bound" in fs[0].message

    def test_named_sharding_axis_checked(self):
        src = """
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(None, axis_names=("data",))
            sh = NamedSharding(mesh, P("model"))
        """
        fs = [f for f in rules_fired(src) if f.rule == "R8"]
        assert len(fs) == 1
        assert "NamedSharding" in fs[0].message

    def test_dynamic_axis_name_silent(self):
        # parameter-fed axis: the caller decides; nothing to check
        src = """
            import jax

            def rollup(x, axis_name):
                return jax.lax.psum(x, axis_name)
        """
        assert "R8" not in rule_set(src)


# ----------------------------------------------------------------------
# R9: lock-order discipline
# ----------------------------------------------------------------------

class TestR9LockOrder:
    BAD_CYCLE = """
        import threading

        class Pair:
            def __init__(self):
                self.l1 = threading.Lock()
                self.l2 = threading.Lock()

            def fwd(self):
                with self.l1:
                    with self.l2:
                        pass

            def rev(self):
                with self.l2:
                    with self.l1:
                        pass
    """

    def test_ab_ba_cycle_fires(self):
        fs = [f for f in rules_fired(self.BAD_CYCLE) if f.rule == "R9"]
        assert len(fs) == 2          # one per conflicting site
        assert all("cycle" in f.message for f in fs)

    def test_consistent_order_silent(self):
        src = self.BAD_CYCLE.replace(
            "with self.l2:\n                    with self.l1:",
            "with self.l1:\n                    with self.l2:")
        assert "R9" not in rule_set(src)

    def test_self_deadlock_via_callee_fires(self):
        src = """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()

                def outer(self):
                    with self._lock:
                        self.helper()

                def helper(self):
                    with self._lock:
                        pass
        """
        fs = [f for f in rules_fired(src) if f.rule == "R9"]
        assert len(fs) == 1
        assert "self-deadlock" in fs[0].message
        # RLock is reentrant: the same shape is legal
        assert "R9" not in rule_set(src.replace("threading.Lock()",
                                                "threading.RLock()"))

    def test_blocking_queue_get_under_lock_fires(self):
        src = """
            import queue
            import threading

            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()

                def drain(self):
                    with self._lock:
                        return self._q.get()
        """
        fs = [f for f in rules_fired(src) if f.rule == "R9"]
        assert len(fs) == 1
        assert "get" in fs[0].message and "holding" in fs[0].message
        assert "R9" not in rule_set(src.replace(
            "self._q.get()", "self._q.get(timeout=1.0)"))

    def test_blocking_join_via_callee_under_lock_fires(self):
        src = """
            import threading

            class Runner:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._t = threading.Thread(target=print, daemon=True)

                def _stop_worker(self):
                    self._t.join()

                def close(self):
                    with self._lock:
                        self._stop_worker()
        """
        fs = [f for f in rules_fired(src, rules=["R9"])]
        assert any("join" in f.message and "_stop_worker" in f.message
                   for f in fs)


# ----------------------------------------------------------------------
# decorator-line suppressions (ISSUE 7 satellite)
# ----------------------------------------------------------------------

class TestDecoratorSuppression:
    BAD = """
        import jax
        from functools import partial
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(None, axis_names=("data",))

        @partial(shard_map, mesh=mesh, in_specs=(P("model"),),
                 out_specs=P("model"))
        def fwd(x):
            return x
    """

    def test_finding_anchored_on_decorated_def_fires(self):
        assert "R8" in rule_set(self.BAD)

    def test_suppression_on_decorator_line_covers_the_def(self):
        # pre-fix, the disable comment on the decorator line was invisible
        # to findings anchored on the decorated def (its lineno is the
        # `def` line, after the decorators)
        src = self.BAD.replace(
            "@partial(shard_map",
            "@partial(  # graftlint: disable=R8 -- staged mesh migration\n"
            "            shard_map")
        assert "R8" not in rule_set(src)

    def test_suppression_is_still_rule_specific(self):
        src = self.BAD.replace(
            "@partial(shard_map",
            "@partial(  # graftlint: disable=R1 -- wrong rule named\n"
            "            shard_map")
        assert "R8" in rule_set(src)


# ----------------------------------------------------------------------
# lint --diff (ISSUE 7 satellite: pre-commit runs are instant)
# ----------------------------------------------------------------------

class TestLintDiff:
    def test_changed_lines_parser(self, tmp_path):
        import subprocess

        from deeplearning4j_tpu.cli import _git_changed_lines

        repo = tmp_path / "r"
        repo.mkdir()

        def git(*args):
            subprocess.run(["git", "-C", str(repo), *args], check=True,
                           capture_output=True,
                           env={"PATH": "/usr/bin:/bin",
                                "GIT_AUTHOR_NAME": "t",
                                "GIT_AUTHOR_EMAIL": "t@t",
                                "GIT_COMMITTER_NAME": "t",
                                "GIT_COMMITTER_EMAIL": "t@t",
                                "HOME": str(tmp_path)})

        git("init", "-q")
        f = repo / "m.py"
        f.write_text("a = 1\nb = 2\nc = 3\n")
        git("add", "m.py")
        git("commit", "-qm", "seed")
        f.write_text("a = 1\nb = 20\nc = 3\nd = 4\ne = 5\n")
        changed = _git_changed_lines("HEAD", str(repo))
        assert changed == {"m.py": {2, 4, 5}}

    def test_untracked_files_count_every_line(self, tmp_path):
        # `git diff REF` omits untracked files entirely; pre-commit must
        # still see a brand-new module's findings
        import subprocess

        from deeplearning4j_tpu.cli import _git_changed_lines

        repo = tmp_path / "r2"
        repo.mkdir()
        env = {"PATH": "/usr/bin:/bin", "GIT_AUTHOR_NAME": "t",
               "GIT_AUTHOR_EMAIL": "t@t", "GIT_COMMITTER_NAME": "t",
               "GIT_COMMITTER_EMAIL": "t@t", "HOME": str(tmp_path)}
        subprocess.run(["git", "-C", str(repo), "init", "-q"], check=True,
                       capture_output=True, env=env)
        (repo / "seed.py").write_text("x = 1\n")
        subprocess.run(["git", "-C", str(repo), "add", "seed.py"],
                       check=True, capture_output=True, env=env)
        subprocess.run(["git", "-C", str(repo), "commit", "-qm", "s"],
                       check=True, capture_output=True, env=env)
        (repo / "fresh.py").write_text("a = 1\nb = 2\n")
        changed = _git_changed_lines("HEAD", str(repo))
        assert changed == {"fresh.py": {1, 2}}

    def test_diff_mode_filters_untouched_findings(self, tmp_path, capsys):
        # a bad file OUTSIDE the repo diff: without --diff it fails the
        # gate, with --diff vs HEAD every finding is off-diff -> clean
        p = tmp_path / "bad.py"
        p.write_text(TestLintCli.BAD)
        assert main(["lint", str(p), "--no-baseline"]) == 1
        capsys.readouterr()
        assert main(["lint", str(p), "--no-baseline", "--diff", "HEAD"]) == 0

    def test_diff_bad_ref_is_usage_error(self, tmp_path):
        p = tmp_path / "ok.py"
        p.write_text("x = 1\n")
        with pytest.raises(SystemExit):
            main(["lint", str(p), "--diff", "not-a-ref-xyz"])


# ----------------------------------------------------------------------
# lint --san-report (static R9 x observed graftsan orders)
# ----------------------------------------------------------------------

class TestSanReportMerge:
    SRC = textwrap.dedent("""
        import threading

        class Pair:
            def __init__(self):
                self.l1 = threading.Lock()
                self.l2 = threading.Lock()

            def fwd(self):
                with self.l1:
                    with self.l2:
                        pass
    """)

    def _report(self, tmp_path, edges, findings=()):
        doc = {"version": 1, "locks": {}, "findings": list(findings),
               "lock_order_edges": [
                   {"from": a, "to": b, "count": 1} for a, b in edges]}
        rp = tmp_path / "gsan.json"
        rp.write_text(json.dumps(doc))
        return rp

    def test_observed_reverse_order_completes_static_cycle(self, tmp_path,
                                                           capsys):
        # static sees only l1->l2; runtime observed l2->l1 (keyed by the
        # locks' ALLOCATION sites). Neither prong alone has a cycle; the
        # merged graph does.
        p = tmp_path / "pair.py"
        p.write_text(self.SRC)
        l1 = f"{p}:6"       # self.l1 = threading.Lock()
        l2 = f"{p}:7"
        rp = self._report(tmp_path, [(l2, l1)])
        rc = main(["lint", str(p), "--san-report", str(rp)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "MERGED lock-order cycle" in out

    def test_consistent_observed_order_clean(self, tmp_path, capsys):
        p = tmp_path / "pair.py"
        p.write_text(self.SRC)
        l1, l2 = f"{p}:6", f"{p}:7"
        rp = self._report(tmp_path, [(l1, l2)])
        rc = main(["lint", str(p), "--san-report", str(rp)])
        assert rc == 0
        assert "merge clean" in capsys.readouterr().out

    def test_runtime_findings_fail_the_merge(self, tmp_path, capsys):
        p = tmp_path / "pair.py"
        p.write_text(self.SRC)
        rp = self._report(tmp_path, [], findings=[
            {"kind": "leaked-thread", "message": "thread 'w' leaked",
             "site": ""}])
        rc = main(["lint", str(p), "--san-report", str(rp)])
        assert rc == 1
        assert "RUNTIME leaked-thread" in capsys.readouterr().out


# ----------------------------------------------------------------------
# hardening regressions (PR 7 review)
# ----------------------------------------------------------------------

class TestDataflowHardening:
    def test_cyclic_alias_chain_does_not_recurse(self):
        # t = a; a = b; b = t on locals fed to a resolvable call once
        # recursed binding_donation forever (RecursionError killed the
        # whole lint run on legal swap code)
        src = """
            import jax

            def helper(fn):
                return fn

            def swap(x):
                t = a
                a = b
                b = t
                helper(a)
                return x
        """
        findings, err = lint_source(textwrap.dedent(src))
        assert err is None
        assert all(f.rule != "E0" for f in findings)

    def test_nonblocking_queue_get_under_lock_silent(self):
        # get(False) / get(block=False) never block: the get_nowait-style
        # drain pattern must not trip R9 (reproduced false positive)
        src = """
            import queue
            import threading

            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()

                def drain_pos(self):
                    with self._lock:
                        return self._q.get(False)

                def drain_kw(self):
                    with self._lock:
                        return self._q.get(block=False)

                def offer(self, item):
                    with self._lock:
                        self._q.put(item, False)
        """
        assert "R9" not in rule_set(src)

    def test_diff_mode_sees_decorator_only_edits(self, tmp_path,
                                                 monkeypatch):
        # an R8 finding anchored on the def line must survive --diff when
        # only its DECORATOR line changed (sup_start covers the range)
        from deeplearning4j_tpu import cli as cli_mod

        p = tmp_path / "dec.py"
        p.write_text(textwrap.dedent(TestDecoratorSuppression.BAD))
        findings = lint_paths([p])
        r8 = [f for f in findings if f.rule == "R8"]
        assert r8 and r8[0].sup_start < r8[0].line
        dec_line = r8[0].sup_start     # the @partial(...) line

        monkeypatch.setattr(
            cli_mod, "_git_changed_lines",
            lambda ref, root: {str(p): {dec_line}})
        assert main(["lint", str(p), "--no-baseline", "--diff", "HEAD"]) == 1
        # an edit elsewhere in the file: finding filtered out
        monkeypatch.setattr(
            cli_mod, "_git_changed_lines",
            lambda ref, root: {str(p): {1}})
        assert main(["lint", str(p), "--no-baseline", "--diff", "HEAD"]) == 0


# ----------------------------------------------------------------------
# R10-R13: wire-contract & telemetry-schema rules (ISSUE 19)
# ----------------------------------------------------------------------

def fleet_rules_fired(src, rules=None, path="pkg/fleet/mod.py"):
    """Lint one dedented source under a fleet-path module name (R12 only
    gates modules whose path mentions fleet/federate)."""
    from deeplearning4j_tpu.analysis import LintModule, lint_modules
    mod = LintModule(textwrap.dedent(src), path=path)
    return lint_modules([mod])


class TestR10WireContract:
    HANDLER = """
        import json
        from urllib.request import urlopen

        class Handler:
            def do_GET(self):
                if self.path.startswith("/health"):
                    self._send(200, {"ok": True, "pid": 1})
                elif self.path == "/stats":
                    self._send(200, {"stats": {}})

            def do_POST(self):
                if self.path.startswith("/submit"):
                    self._send(200, {"outputs": []})
    """

    def test_route_typo_fires(self):
        src = self.HANDLER + """
        def client(addr):
            code, doc = _http_json(addr + "/helth", {})
            return code
        """
        fs = [f for f in rules_fired(src) if f.rule == "R10"]
        assert len(fs) == 1
        assert "/helth" in fs[0].message
        assert "no handler serves it" in fs[0].message

    def test_served_route_silent(self):
        src = self.HANDLER + """
        def client(addr):
            code, doc = _http_json(addr + "/health", {})
            code, doc = _http_json(addr + "/submit?x=1", {})
            return code
        """
        assert "R10" not in {f.rule for f in rules_fired(src)}

    def test_unknown_response_key_fires(self):
        src = self.HANDLER + """
        def client(addr):
            code, doc = _http_json(addr + "/stats", {})
            return doc["latency"]
        """
        fs = [f for f in rules_fired(src) if f.rule == "R10"]
        assert len(fs) == 1
        assert "'latency'" in fs[0].message

    def test_emitted_response_key_silent(self):
        src = self.HANDLER + """
        def client(addr):
            code, doc = _http_json(addr + "/stats", {})
            return doc["stats"], doc.get("ok")
        """
        assert "R10" not in {f.rule for f in rules_fired(src)}

    def test_subscript_assigned_key_counts_as_emitted(self):
        # worker.py emits resp["trace"] = ... by subscript, not dict
        # literal — the harvest must see it (reproduced false positive)
        src = self.HANDLER.replace(
            'self._send(200, {"stats": {}})',
            'resp = {}\n'
            '                resp["trace"] = self._trace_doc()\n'
            '                self._send(200, resp)') + """
        def client(addr):
            code, doc = _http_json(addr + "/stats", {})
            return doc.get("trace")
        """
        assert "R10" not in {f.rule for f in rules_fired(src)}

    def test_header_drift_fires_on_minority_spelling(self):
        src = """
            TRACE = "X-DL4J-Trace-Id"

            def stamp(headers):
                headers["X-DL4J-Trace-Id"] = "t1"

            def read(headers):
                return headers.get("X-Dl4j-Trace-ID")
        """
        fs = [f for f in rules_fired(src) if f.rule == "R10"]
        assert len(fs) == 1
        assert "X-Dl4j-Trace-ID" in fs[0].message
        assert "majority" in fs[0].message

    def test_consistent_headers_silent(self):
        src = """
            TRACE = "X-DL4J-Trace-Id"
            ORIGIN = "X-DL4J-Origin"

            def stamp(headers):
                headers[TRACE] = "t1"
                headers[ORIGIN] = "probe"
        """
        assert "R10" not in {f.rule for f in rules_fired(src)}

    def test_no_handlers_no_route_findings(self):
        # a client-only module (single-file lint) has no route registry
        # to check against — silence, not a storm of unknown routes
        src = """
            def client(addr):
                code, doc = _http_json(addr + "/anything", {})
                return doc["whatever"]
        """
        assert "R10" not in {f.rule for f in rules_fired(src)}


class TestR11MetricSchema:
    def test_disjoint_label_sets_fire(self):
        src = """
            import telemetry as _tm

            class S:
                def __init__(self):
                    reg = _tm.get_registry()
                    self._m = reg.counter("requests_total", "requests")

                def a(self):
                    self._m.inc(model="m")

                def b(self):
                    self._m.inc(worker="w")
        """
        fs = [f for f in rules_fired(src) if f.rule == "R11"]
        assert len(fs) == 1
        assert "requests_total" in fs[0].message
        assert "must nest" in fs[0].message

    def test_subset_label_sets_silent(self):
        # the optional-label idiom (origin rides **olab sometimes) is
        # legal: one site's keys nest inside the other's
        src = """
            import telemetry as _tm

            class S:
                def __init__(self):
                    reg = _tm.get_registry()
                    self._m = reg.counter("requests_total", "requests")

                def a(self):
                    self._m.inc(model="m")

                def b(self):
                    self._m.inc(model="m", origin="probe")
        """
        assert "R11" not in {f.rule for f in rules_fired(src)}

    def test_referenced_but_never_created_fires(self):
        src = """
            import telemetry

            def read():
                return telemetry.series_map("ghost_total")
        """
        fs = [f for f in rules_fired(src) if f.rule == "R11"]
        assert len(fs) == 1
        assert "ghost_total" in fs[0].message

    def test_referenced_and_created_silent(self):
        src = """
            import telemetry as _tm

            def make(reg):
                return reg.counter("real_total", "is real")

            def read():
                return _tm.series_map("real_total")
        """
        assert "R11" not in {f.rule for f in rules_fired(src)}

    def test_slo_rule_reference_fires(self):
        src = """
            from telemetry.slo import SloRule

            RULES = [SloRule("probe_fail", "ratio", "ghost_bad_total",
                             den_metric="ghost_total")]
        """
        fs = [f for f in rules_fired(src) if f.rule == "R11"]
        assert {("ghost_bad_total" in f.message or
                 "ghost_total" in f.message) for f in fs} == {True}
        assert len(fs) == 2

    def test_prefix_dynamic_creation_satisfies_reference(self):
        src = """
            import telemetry as _tm

            def make(reg, key):
                return reg.gauge(f"worker_{key}", "per-worker")

            def read():
                return _tm.series_map("worker_nonfinite")
        """
        assert "R11" not in {f.rule for f in rules_fired(src)}

    def test_fire_before_register_fires(self):
        # the PR 18 prober bug, pre-fix shape: a verdict-labeled counter
        # whose series only exist once the outcome first happens
        src = """
            import telemetry as _tm

            class Prober:
                def __init__(self):
                    self._reg = _tm.get_registry()
                    self._m_total = self._reg.counter(
                        "probe_total", "probes by verdict")

                def probe_once(self, verdict):
                    self._m_total.inc(model="m", verdict=verdict)
        """
        fs = [f for f in rules_fired(src) if f.rule == "R11"]
        assert len(fs) == 1
        assert "probe_total" in fs[0].message
        assert "pre-registered" in fs[0].message

    def test_preregistered_counter_silent(self):
        # the prober idiom post-fix: inc(0, ...) per enum value at init
        src = """
            import telemetry as _tm

            VERDICTS = ("ok", "error")

            class Prober:
                def __init__(self):
                    self._reg = _tm.get_registry()
                    self._m_total = self._reg.counter(
                        "probe_total", "probes by verdict")
                    if self._reg.enabled:
                        for verdict in VERDICTS:
                            self._m_total.inc(0, model="m",
                                              verdict=verdict)

                def probe_once(self, verdict):
                    self._m_total.inc(model="m", verdict=verdict)
        """
        assert "R11" not in {f.rule for f in rules_fired(src)}


class TestR12BlockingTimeout:
    def test_urlopen_without_timeout_fires_on_fleet_path(self):
        src = """
            from urllib.request import urlopen

            def scrape(url):
                with urlopen(url) as r:
                    return r.read()
        """
        fs = [f for f in fleet_rules_fired(src) if f.rule == "R12"]
        assert len(fs) == 1
        assert "urlopen" in fs[0].message

    def test_urlopen_with_timeout_silent(self):
        src = """
            from urllib.request import urlopen

            def scrape(url):
                with urlopen(url, timeout=5.0) as r:
                    return r.read()
        """
        assert "R12" not in {f.rule for f in fleet_rules_fired(src)}

    def test_ungated_path_not_flagged(self):
        # the same timeout-less call OUTSIDE fleet/federate paths is not
        # R12's business (R12 polices the wire tier, not the whole repo)
        src = """
            from urllib.request import urlopen

            def scrape(url):
                return urlopen(url).read()
        """
        fs = fleet_rules_fired(src, path="pkg/datasets/fetch.py")
        assert "R12" not in {f.rule for f in fs}

    def test_bare_join_and_get_fire(self):
        src = """
            def wait(thread, q):
                thread.join()
                return q.get()
        """
        fs = [f for f in fleet_rules_fired(src) if f.rule == "R12"]
        assert len(fs) == 2

    def test_bounded_join_get_communicate_silent(self):
        src = """
            def wait(thread, q, proc):
                thread.join(timeout=5.0)
                out = proc.communicate(timeout=10.0)
                return q.get(timeout=1.0), out
        """
        assert "R12" not in {f.rule for f in fleet_rules_fired(src)}

    def test_communicate_without_timeout_fires(self):
        src = """
            def reap(proc):
                return proc.communicate()
        """
        fs = [f for f in fleet_rules_fired(src) if f.rule == "R12"]
        assert len(fs) == 1

    def test_unbounded_queue_put_silent_bounded_fires(self):
        src = """
            import queue

            class Router:
                def __init__(self):
                    self._open = queue.Queue()
                    self._tight = queue.Queue(8)

                def enqueue(self, item):
                    self._open.put(item)      # unbounded: never blocks

                def admit(self, item):
                    self._tight.put(item)     # bounded: producer hang
        """
        fs = [f for f in fleet_rules_fired(src) if f.rule == "R12"]
        assert len(fs) == 1
        assert "_tight" in fs[0].message

    def test_str_join_not_flagged(self):
        src = """
            def fmt(parts):
                return ", ".join(parts)
        """
        assert "R12" not in {f.rule for f in fleet_rules_fired(src)}


class TestR13LabelCardinality:
    def test_raw_path_label_fires(self):
        src = """
            import telemetry as _tm

            class H:
                def __init__(self):
                    reg = _tm.get_registry()
                    self._m = reg.counter("http_total", "requests")

                def count(self, path):
                    self._m.inc(path=path)
        """
        fs = [f for f in rules_fired(src) if f.rule == "R13"]
        assert len(fs) == 1
        assert "raw request path" in fs[0].message

    def test_derived_path_local_fires(self):
        # the pre-fix worker shape: a local derived from the raw path
        src = """
            import telemetry as _tm

            class H:
                def __init__(self):
                    reg = _tm.get_registry()
                    self._m = reg.counter("http_total", "requests")

                def count(self, path):
                    root = "/" + path.split("/")[0]
                    self._m.inc(path=root)
        """
        fs = [f for f in rules_fired(src) if f.rule == "R13"]
        assert len(fs) == 1

    def test_closed_set_bucketing_silent(self):
        # the fix idiom: x if x in KNOWN else "other"
        src = """
            import telemetry as _tm

            ROUTES = ("/health", "/stats")

            class H:
                def __init__(self):
                    reg = _tm.get_registry()
                    self._m = reg.counter("http_total", "requests")

                def count(self, path):
                    root = "/" + path.split("/")[0]
                    root = root if root in ROUTES else "/other"
                    self._m.inc(path=root)
        """
        assert "R13" not in {f.rule for f in rules_fired(src)}

    def test_exception_text_label_fires(self):
        src = """
            import telemetry as _tm

            class H:
                def __init__(self):
                    reg = _tm.get_registry()
                    self._m = reg.counter("errors_total", "errors")

                def run(self, fn):
                    try:
                        fn()
                    except Exception as e:
                        self._m.inc(error=str(e))
        """
        fs = [f for f in rules_fired(src) if f.rule == "R13"]
        assert len(fs) == 1
        assert "exception text" in fs[0].message

    def test_enum_literal_label_silent(self):
        src = """
            import telemetry as _tm

            class H:
                def __init__(self):
                    reg = _tm.get_registry()
                    self._m = reg.counter("errors_total", "errors")

                def run(self):
                    self._m.inc(outcome="ok", model="m")
        """
        assert "R13" not in {f.rule for f in rules_fired(src)}


class TestContractRulesCleanAtHead:
    def test_no_contract_findings_with_empty_baseline(self):
        # ISSUE 19 acceptance: R10-R13 surface nothing at HEAD (findings
        # were FIXED, not baselined) and the ledger holds zero entries
        findings = lint_paths([PKG], root=REPO,
                              rules=["R10", "R11", "R12", "R13"])
        assert findings == [], "\n".join(f.human() for f in findings)
        assert load_baseline(REPO / "graftlint.baseline.json") == {}

    def test_worker_http_counter_buckets_paths(self):
        # the R13 finding at HEAD stays fixed: the wire counter buckets
        # through GET_ROUTES instead of minting a series per raw path
        src = (PKG / "fleet" / "worker.py").read_text()
        assert "root if root in GET_ROUTES" in src
        assert "graftlint: disable=R13" not in src

    def test_enum_counters_preregister_at_zero(self):
        # the PR 18 prober-class sweep stays swept: every verdict/
        # outcome counter pre-registers with inc(0, ...) at init
        for rel in ("fleet/router.py", "serving/engine.py",
                    "continuous/trainer.py", "telemetry/history.py",
                    "telemetry/federate.py", "hostfleet/supervisor.py",
                    "parallel/distributed.py", "datasets/iterator.py",
                    "datasets/cacheable.py"):
            src = (PKG / rel).read_text()
            assert ".inc(0," in src, rel


class TestSchemaArtifact:
    def test_schema_regenerates_deterministically(self):
        from deeplearning4j_tpu.analysis import build_schema, parse_paths
        from deeplearning4j_tpu.analysis.reporters import schema_json_text

        mods1, e1 = parse_paths([PKG], root=REPO)
        mods2, e2 = parse_paths([PKG], root=REPO)
        assert e1 == [] and e2 == []
        assert (schema_json_text(build_schema(mods1))
                == schema_json_text(build_schema(mods2)))

    def test_committed_artifact_matches_source(self):
        # the tier-1 drift gate's exact comparison, as a test: SCHEMA.json
        # and METRICS.md at HEAD are the contract the source harvests to
        from deeplearning4j_tpu.analysis import build_schema, parse_paths
        from deeplearning4j_tpu.analysis.reporters import (metrics_md_text,
                                                           schema_json_text)

        mods, errs = parse_paths([PKG], root=REPO)
        assert errs == []
        schema = build_schema(mods)
        assert (REPO / "SCHEMA.json").read_text() == schema_json_text(schema)
        assert (REPO / "METRICS.md").read_text() == metrics_md_text(schema)

    def test_schema_covers_the_load_bearing_series(self):
        schema = json.loads((REPO / "SCHEMA.json").read_text())
        for name in ("fleet_requests_total", "probe_total",
                     "serving_model_requests_total", "slo_alerts_total",
                     "federate_scrape_total"):
            assert name in schema["metrics"], name
        assert schema["metrics"]["probe_total"]["preregistered"]
        assert "verdict" in (schema["metrics"]["probe_total"]["labels"]
                             + schema["metrics"]["probe_total"]
                             ["optional_labels"])
        routes = {r["path"] for r in schema["wire"]["routes"]}
        assert {"/submit", "/health", "/metrics"} <= routes
        assert "X-DL4J-Trace-Id" in schema["wire"]["headers"]

    def test_emit_schema_cli_writes_both_artifacts(self, tmp_path):
        rc = main(["lint", "--emit-schema", "--schema-dir",
                   str(tmp_path)])
        assert rc == 0
        assert (tmp_path / "SCHEMA.json").exists()
        assert (tmp_path / "METRICS.md").exists()
        got = json.loads((tmp_path / "SCHEMA.json").read_text())
        assert got == json.loads((REPO / "SCHEMA.json").read_text())
