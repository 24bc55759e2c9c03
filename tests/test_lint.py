"""Unit tests for bigdl_tpu.lint: every rule fires on its fixture and
stays quiet on the negative twin; suppressions, baseline workflow,
reporters, and the CLI round out the engine."""

import json
import textwrap

from bigdl_tpu.lint import (Finding, lint_file, lint_paths, load_baseline,
                            write_baseline)
from bigdl_tpu.lint.__main__ import main as lint_main
from bigdl_tpu.lint.reporters import json_report, text_report
from bigdl_tpu.lint.rules import ALL_RULES, RULES_BY_NAME


def lint_src(tmp_path, source, select=None, name="fixture.py", root=None):
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    rules = [RULES_BY_NAME[s] for s in select] if select else None
    return lint_file(str(f), rules=rules, root=root)


def rules_of(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------- host-sync-in-jit

def test_host_sync_fires_on_jitted_fn(tmp_path):
    findings = lint_src(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def step(params, x):
            loss = (x * x).sum()
            print(loss)
            host = np.asarray(loss)
            return float(loss) + loss.item() + host
        """, select=["host-sync-in-jit"])
    assert len(findings) == 4  # print, np.asarray, float, .item
    assert all(f.rule == "host-sync-in-jit" for f in findings)


def test_host_sync_quiet_outside_trace_and_on_shapes(tmp_path):
    findings = lint_src(tmp_path, """
        import jax
        import numpy as np

        def host_loop(arr):
            print(arr)                    # host code: fine
            return float(np.asarray(arr)[0])

        @jax.jit
        def step(x):
            n = int(x.shape[0])           # shape math is static
            jax.debug.print("n={}", n)    # the sanctioned print
            return x.reshape(n, -1)
        """, select=["host-sync-in-jit"])
    assert findings == []


def test_host_sync_reaches_through_call_graph(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        def helper(v):
            return v.item()

        @jax.jit
        def step(x):
            return helper(x)
        """, select=["host-sync-in-jit"])
    assert len(findings) == 1
    assert "helper" in findings[0].message


def test_host_sync_sees_scan_body_and_shard_map(tmp_path):
    findings = lint_src(tmp_path, """
        import jax
        from jax import shard_map

        def outer(xs):
            def body(carry, x):
                print(x)
                return carry, x
            return jax.lax.scan(body, 0, xs)

        def local(x):
            return float(x)

        step = shard_map(local, mesh=None, in_specs=None, out_specs=None)
        """, select=["host-sync-in-jit"])
    assert len(findings) == 2


# ---------------------------------------------------------- missing-donation

def test_missing_donation_fires_on_call_and_decorator(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        def step(params, opt_state, batch):
            return params, opt_state

        train = jax.jit(step)

        @jax.jit
        def update(params, grads):
            return params
        """, select=["missing-donation"])
    assert len(findings) == 2


def test_missing_donation_quiet_when_donating_or_stateless(tmp_path):
    findings = lint_src(tmp_path, """
        import functools
        import jax

        def step(params, opt_state, batch):
            return params, opt_state

        train = jax.jit(step, donate_argnums=(0, 1))

        @functools.partial(jax.jit, donate_argnames=("params",))
        def update(params, grads):
            return params

        @jax.jit
        def pure_math(x, y):
            return x + y
        """, select=["missing-donation"])
    assert findings == []


def test_missing_donation_fires_on_lambda(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        def serve(model):
            return jax.jit(lambda p, s, v: model.apply(p, s, v)[0])
        """, select=["missing-donation"])
    assert len(findings) == 1


def test_missing_donation_suppressible_inline(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        def calibrate(run, params, state, x):
            # params are reused right after: donation would be wrong
            # jaxlint: disable-next-line=missing-donation
            return jax.jit(run)(params, state, x)

        def run(params, state, x):
            return params
        """, select=["missing-donation"])
    assert findings == []


# ----------------------------------------------------------------- key-reuse

def test_key_reuse_fires_on_double_draw(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        def sample(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
        """, select=["key-reuse"])
    assert len(findings) == 1


def test_key_reuse_quiet_with_split_or_fold_in(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        def sample(key):
            k1, k2 = jax.random.split(key)
            return jax.random.normal(k1, (3,)) + jax.random.uniform(k2, (3,))

        def layers(rng, xs):
            out = []
            for i, x in enumerate(xs):
                out.append(jax.random.fold_in(rng, i))
            return out
        """, select=["key-reuse"])
    assert findings == []


def test_key_reuse_fires_in_loop_without_resplit(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        def draws(key):
            out = []
            for _ in range(3):
                out.append(jax.random.normal(key, ()))
            return out
        """, select=["key-reuse"])
    assert len(findings) == 1


def test_key_reuse_seed_fanout(tmp_path):
    findings = lint_src(tmp_path, """
        import numpy as np

        def build(seed):
            a = np.random.default_rng(seed)
            b = np.random.default_rng(seed)
            return a, b
        """, select=["key-reuse"])
    assert len(findings) == 1
    assert "correlated" in findings[0].message


def test_key_reuse_seed_fanout_quiet_with_subseeds(tmp_path):
    findings = lint_src(tmp_path, """
        import numpy as np

        def build(seed):
            subs = np.random.SeedSequence(seed).generate_state(2)
            a = np.random.default_rng(subs[0])
            b = np.random.default_rng(subs[1])
            return a, b

        def single(seed):
            return np.random.default_rng(seed)
        """, select=["key-reuse"])
    assert findings == []


# --------------------------------------------------------------- tracer-leak

def test_tracer_leak_fires_on_self_and_global(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        _stats = None

        class M:
            @jax.jit
            def step(self, x):
                self.cache = x * 2
                return x

        @jax.jit
        def f(x):
            global _stats
            _stats = x
            return x
        """, select=["tracer-leak"])
    assert len(findings) == 2


def test_tracer_leak_quiet_on_host_and_constants(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        class M:
            def host_setup(self, x):
                self.cache = x * 2     # not traced: fine
                return x

            @jax.jit
            def step(self, x):
                y = x * 2              # local: fine
                return y
        """, select=["tracer-leak"])
    assert findings == []


# ----------------------------------------------------------------- np-vs-jnp

def test_np_vs_jnp_fires_inside_jit(tmp_path):
    findings = lint_src(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            noise = np.random.uniform(size=(3,))
            return np.sum(x) + noise
        """, select=["np-vs-jnp"])
    assert len(findings) == 2
    assert "trace time" in findings[0].message


def test_np_vs_jnp_quiet_on_trace_constants_and_jnp(tmp_path):
    findings = lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def f(x):
            mask = np.zeros(4)       # trace-time constant: idiomatic
            return jnp.sum(x) + mask
        """, select=["np-vs-jnp"])
    assert findings == []


def test_np_vs_jnp_flags_jnp_in_host_pipeline_module(tmp_path):
    findings = lint_src(tmp_path, """
        import jax.numpy as jnp

        def preprocess(img):
            return jnp.asarray(img) / 255.0
        """, select=["np-vs-jnp"], name="transform/pipeline.py",
        root=str(tmp_path))
    assert len(findings) == 1
    assert "host-only" in findings[0].message


def test_np_vs_jnp_host_pipeline_quiet_with_numpy(tmp_path):
    findings = lint_src(tmp_path, """
        import numpy as np

        def preprocess(img):
            return np.asarray(img) / 255.0
        """, select=["np-vs-jnp"], name="transform/pipeline.py",
        root=str(tmp_path))
    assert findings == []


# ----------------------------------------------------------- recompile-hazard

def test_recompile_hazard_shape_branch_and_frozen_reads(tmp_path):
    findings = lint_src(tmp_path, """
        import time

        import jax

        @jax.jit
        def f(x):
            if x.shape[0] > 4:
                return x * 2
            return x * time.time()
        """, select=["recompile-hazard"])
    assert len(findings) == 2


def test_recompile_hazard_loop_capture(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        def outer(xs, x):
            for i in range(3):
                total = i

            @jax.jit
            def inner(v):
                return v + i
            return inner(x)
        """, select=["recompile-hazard"])
    assert len(findings) == 1
    assert "loop variable" in findings[0].message


def test_recompile_hazard_quiet_on_conditional_init(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        def outer(flag, x):
            scale = 1.0
            if flag:
                scale = 2.0

            @jax.jit
            def inner(v):
                return v * scale
            return inner(x)

        def per_item(xs):
            outs = []
            for x in xs:
                @jax.jit
                def one(v):
                    return v + x          # def inside the loop: rebuilt
                outs.append(one(x))
            return outs
        """, select=["recompile-hazard"])
    assert findings == []


def test_recompile_hazard_accumulator_capture(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        def outer(xs, x):
            count = 0
            for y in xs:
                count += 1

            @jax.jit
            def inner(v):
                return v + count
            return inner(x)
        """, select=["recompile-hazard"])
    assert len(findings) == 1
    assert "accumulator" in findings[0].message


# ---------------------------------------------------------------- span-in-jit

def test_span_in_jit_fires_on_spans_and_metric_mutations(tmp_path):
    findings = lint_src(tmp_path, """
        import jax
        from bigdl_tpu import obs

        steps = obs.counter("steps_total")

        @jax.jit
        def step(params, x):
            with obs.span("train/dispatch"):
                y = x * 2
            obs.record_span("train/feed", 0.0, 1.0)
            steps.inc()
            obs.histogram("step_seconds").observe(0.1)
            return y
        """, select=["span-in-jit"])
    # obs.span, obs.record_span, steps.inc, .observe
    # (obs.histogram() itself resolves under bigdl_tpu.obs too)
    assert len(findings) >= 4
    assert all(f.rule == "span-in-jit" for f in findings)
    assert any(".observe()" in f.message for f in findings)


def test_span_in_jit_quiet_on_host_side_and_tick(tmp_path):
    findings = lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp
        from bigdl_tpu import obs
        from bigdl_tpu.utils.profiling import DecodeCounters

        stats = DecodeCounters("traces")

        @jax.jit
        def step(params, x, idx):
            stats.tick("traces")       # sanctioned: counts compiles
            return x.at[idx].set(0.0)  # jnp .set is not a Gauge.set

        def host_loop(x):
            with obs.span("train/dispatch"):   # host side: fine
                out = step(None, x, 0)
            obs.counter("steps_total").inc()
            return out
        """, select=["span-in-jit"])
    assert findings == []


# ------------------------------------------------------- engine mechanics

def test_suppression_same_line_and_all(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            print(x)  # jaxlint: disable=host-sync-in-jit
            print(x)  # jaxlint: disable
            print(x)  # jaxlint: disable=key-reuse
            return x
        """, select=["host-sync-in-jit"])
    assert len(findings) == 1  # only the wrong-rule suppression fires


def test_parse_error_is_a_finding(tmp_path):
    findings = lint_src(tmp_path, "def broken(:\n    pass\n")
    assert rules_of(findings) == ["parse-error"]


def test_fingerprint_stable_under_line_insertion(tmp_path):
    src = """
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """
    (f1,) = lint_src(tmp_path, src, select=["host-sync-in-jit"])
    shifted = src.replace("import jax",
                          "import jax\n\n        # a new comment")
    (f2,) = lint_src(tmp_path, shifted, select=["host-sync-in-jit"])
    assert f1.line != f2.line
    assert f1.fingerprint == f2.fingerprint


def test_baseline_workflow(tmp_path):
    fix = tmp_path / "mod.py"
    fix.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """))
    base = tmp_path / "baseline.json"

    first = lint_paths([str(fix)], baseline_path=str(base),
                       root=str(tmp_path))
    assert len(first.new_findings) == 1

    write_baseline(str(base), first.findings)
    assert len(load_baseline(str(base))) == 1

    second = lint_paths([str(fix)], baseline_path=str(base),
                        root=str(tmp_path))
    assert second.new_findings == []
    assert second.baselined_count == 1

    # a NEW violation still fails even with the old one baselined
    fix.write_text(fix.read_text() + textwrap.dedent("""
        @jax.jit
        def g(y):
            return y.item()
        """))
    third = lint_paths([str(fix)], baseline_path=str(base),
                       root=str(tmp_path))
    assert len(third.new_findings) == 1
    assert third.new_findings[0].line > 5


def test_reporters(tmp_path):
    fix = tmp_path / "mod.py"
    fix.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return float(x)\n")
    result = lint_paths([str(fix)], baseline_path=None, root=str(tmp_path))

    text = text_report(result)
    assert "mod.py:5" in text
    assert "1 new finding(s)" in text

    data = json.loads(json_report(result))
    assert data["new_count"] == 1
    assert data["findings"][0]["rule"] == "host-sync-in-jit"
    assert data["findings"][0]["new"] is True


def test_cli_exit_codes_and_list_rules(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("def f(x):\n    return x\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax\n\n@jax.jit\ndef f(x):\n"
                     "    return float(x)\n")

    assert lint_main([str(clean), "--no-baseline"]) == 0
    assert lint_main([str(dirty), "--no-baseline"]) == 1
    assert lint_main([str(dirty), "--no-baseline",
                      "--select", "key-reuse"]) == 0
    assert lint_main(["--select", "no-such-rule", str(dirty)]) == 2

    capsys.readouterr()
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ALL_RULES:
        assert rule.name in out


def test_cli_json_format(tmp_path, capsys):
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax\n\n@jax.jit\ndef f(x):\n"
                     "    return float(x)\n")
    assert lint_main([str(dirty), "--no-baseline", "--format",
                      "json"]) == 1
    data = json.loads(capsys.readouterr().out)
    assert data["new_count"] == 1


def test_finding_str_is_clickable():
    f = Finding(rule="key-reuse", path="bigdl_tpu/x.py", line=3, col=7,
                message="boom")
    assert str(f) == "bigdl_tpu/x.py:3:7: [key-reuse] boom"


# ==================================================== interprocedural (v2)

def lint_project(tmp_path, files, select=None):
    """Write a multi-module fixture tree and lint it as one project."""
    for name, source in files.items():
        f = tmp_path / name
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent(source))
    rules = [RULES_BY_NAME[s] for s in select] if select else None
    result = lint_paths([str(tmp_path)], rules=rules, baseline_path=None,
                        root=str(tmp_path))
    assert result.errors == []
    return result.findings


# ------------------------------------------------------ alias-into-donation

def test_alias_into_donation_pr6_checkpoint_restore(tmp_path):
    """The PR 6 bug, reconstructed across modules: pickle.load in a
    checkpoint helper aliases host storage into ``self.state``, which a
    later method passes in a donated position."""
    findings = lint_project(tmp_path, {
        "ckptio.py": """
            import pickle

            def load_state(path):
                with open(path, "rb") as f:
                    return pickle.load(f)
            """,
        "trainer.py": """
            import jax
            from ckptio import load_state

            class Trainer:
                def __init__(self, params):
                    self.params = params
                    self.state = None
                    self.step_fn = jax.jit(lambda p, s: (p, s),
                                           donate_argnums=(1,))

                def restore(self, path):
                    self.state = load_state(path)

                def train_step(self):
                    self.params, self.state = self.step_fn(
                        self.params, self.state)
            """,
    }, select=["alias-into-donation"])
    assert rules_of(findings) == ["alias-into-donation"]
    assert findings[0].path == "trainer.py"
    assert "pickle.load" in findings[0].message


def test_alias_into_donation_quiet_with_owning_copy(tmp_path):
    findings = lint_project(tmp_path, {
        "ckptio.py": """
            import pickle

            def load_state(path):
                with open(path, "rb") as f:
                    return pickle.load(f)
            """,
        "trainer.py": """
            import jax
            import jax.numpy as jnp
            from ckptio import load_state

            class Trainer:
                def __init__(self, params):
                    self.params = params
                    self.state = None
                    self.step_fn = jax.jit(lambda p, s: (p, s),
                                           donate_argnums=(1,))

                def restore(self, path):
                    # the owning copy breaks the host alias
                    self.state = jnp.array(load_state(path))

                def train_step(self):
                    self.params, self.state = self.step_fn(
                        self.params, self.state)
            """,
    }, select=["alias-into-donation"])
    assert findings == []


# --------------------------------------------------------- use-after-donate

def test_use_after_donate_fires_on_stale_read(tmp_path):
    findings = lint_project(tmp_path, {
        "run.py": """
            import jax

            step = jax.jit(lambda s: s * 2, donate_argnums=(0,))

            def advance(state):
                out = step(state)
                return state.sum() + out.sum()
            """,
    }, select=["use-after-donate"])
    assert rules_of(findings) == ["use-after-donate"]
    assert "donated position 0" in findings[0].message


def test_use_after_donate_quiet_on_returned_value(tmp_path):
    findings = lint_project(tmp_path, {
        "run.py": """
            import jax

            step = jax.jit(lambda s: s * 2, donate_argnums=(0,))

            def advance(state):
                state = step(state)
                return state.sum()
            """,
    }, select=["use-after-donate"])
    assert findings == []


# ----------------------------------------------------- escaping-donated-ref

def test_escaping_donated_ref_background_writer(tmp_path):
    """The PR 6 checkpoint-writer shape: a background thread serializes
    an attribute the owner thread keeps passing in a donated position."""
    findings = lint_project(tmp_path, {
        "trainer.py": """
            import pickle
            import threading
            import jax

            class Trainer:
                def __init__(self, params, state):
                    self.params = params
                    self.state = state
                    self.step_fn = jax.jit(lambda p, s: (p, s),
                                           donate_argnums=(1,))
                    self._saver = threading.Thread(
                        target=self._save_loop, daemon=True)
                    self._saver.start()

                def train_step(self):
                    self.params, self.state = self.step_fn(
                        self.params, self.state)

                def _save_loop(self):
                    with open("ckpt.bin", "wb") as f:
                        pickle.dump(self.state, f)
            """,
    }, select=["escaping-donated-ref"])
    assert rules_of(findings) == ["escaping-donated-ref"]
    assert "donated position" in findings[0].message


def test_escaping_donated_ref_quiet_with_host_snapshot(tmp_path):
    findings = lint_project(tmp_path, {
        "trainer.py": """
            import pickle
            import threading
            import jax

            class Trainer:
                def __init__(self, params, state):
                    self.params = params
                    self.state = state
                    self.step_fn = jax.jit(lambda p, s: (p, s),
                                           donate_argnums=(1,))
                    self._saver = threading.Thread(
                        target=self._save_loop, daemon=True)
                    self._saver.start()

                def train_step(self):
                    self.params, self.state = self.step_fn(
                        self.params, self.state)

                def _save_loop(self):
                    snap = jax.device_get(self.state)
                    with open("ckpt.bin", "wb") as f:
                        pickle.dump(snap, f)
            """,
    }, select=["escaping-donated-ref"])
    assert findings == []


# ------------------------------------------------- unlocked-shared-mutation

def test_unlocked_shared_mutation_pool_stats_read(tmp_path):
    """The pool_stats shape across modules: the scheduler thread
    structurally mutates the pool's table while ``engine.metrics()``
    (caller thread) reads it with no common lock."""
    findings = lint_project(tmp_path, {
        "pool.py": """
            import jax
            import jax.numpy as jnp

            class SlotPool:
                def __init__(self):
                    self.table = {}
                    self._step_fn = jax.jit(lambda c: c + 1)

                def step(self):
                    self.table["x"] = 1
                    return self._step_fn(jnp.zeros(()))

                def stats(self):
                    return dict(self.table)
            """,
        "engine.py": """
            import threading
            from pool import SlotPool

            class Engine:
                def __init__(self):
                    self.pool = SlotPool()
                    self._thread = threading.Thread(target=self._loop,
                                                    daemon=True)
                    self._thread.start()

                def _loop(self):
                    while True:
                        self.pool.step()

                def metrics(self):
                    return self.pool.stats()
            """,
    }, select=["unlocked-shared-mutation"])
    assert rules_of(findings) == ["unlocked-shared-mutation"]
    assert findings[0].path == "pool.py"
    assert "self.table" in findings[0].message


def test_unlocked_shared_mutation_quiet_on_snapshot_publish(tmp_path):
    """Rebinding an immutable snapshot is the sanctioned lock-free
    publish: the mutated structure stays single-owner."""
    findings = lint_project(tmp_path, {
        "pool.py": """
            import jax
            import jax.numpy as jnp

            class SlotPool:
                def __init__(self):
                    self.table = {}
                    self._snapshot = {}
                    self._step_fn = jax.jit(lambda c: c + 1)

                def step(self):
                    self.table["x"] = 1
                    self._snapshot = dict(self.table)
                    return self._step_fn(jnp.zeros(()))

                def stats(self):
                    return self._snapshot
            """,
        "engine.py": """
            import threading
            from pool import SlotPool

            class Engine:
                def __init__(self):
                    self.pool = SlotPool()
                    self._thread = threading.Thread(target=self._loop,
                                                    daemon=True)
                    self._thread.start()

                def _loop(self):
                    while True:
                        self.pool.step()

                def metrics(self):
                    return self.pool.stats()
            """,
    }, select=["unlocked-shared-mutation"])
    assert findings == []


def test_unlocked_shared_mutation_quiet_with_common_lock(tmp_path):
    findings = lint_project(tmp_path, {
        "engine.py": """
            import threading

            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.table = {}
                    self._thread = threading.Thread(target=self._loop,
                                                    daemon=True)
                    self._thread.start()

                def _loop(self):
                    while True:
                        with self._lock:
                            self.table["x"] = 1

                def metrics(self):
                    with self._lock:
                        return dict(self.table)
            """,
    }, select=["unlocked-shared-mutation"])
    assert findings == []


# -------------------------------------------- foreign-thread-device-access

def test_foreign_thread_device_access_fires(tmp_path):
    findings = lint_project(tmp_path, {
        "pool.py": """
            import jax
            import jax.numpy as jnp

            class SlotPool:
                def __init__(self):
                    self._step_fn = jax.jit(lambda c: c + 1)

                def step(self):
                    return self._step_fn(jnp.zeros(()))
            """,
        "engine.py": """
            import threading
            from pool import SlotPool

            class Engine:
                def __init__(self):
                    self.pool = SlotPool()
                    self._thread = threading.Thread(target=self._loop,
                                                    daemon=True)
                    self._thread.start()

                def _loop(self):
                    while True:
                        self.pool.step()

                def poke(self):
                    # caller thread reaches the jitted dispatch directly
                    return self.pool.step()
            """,
    }, select=["foreign-thread-device-access"])
    assert rules_of(findings) == ["foreign-thread-device-access"]
    assert "SlotPool.step" in findings[0].message


def test_foreign_thread_device_access_quiet_single_owner(tmp_path):
    findings = lint_project(tmp_path, {
        "pool.py": """
            import jax
            import jax.numpy as jnp

            class SlotPool:
                def __init__(self):
                    self._step_fn = jax.jit(lambda c: c + 1)
                    self.last = 0

                def step(self):
                    return self._step_fn(jnp.zeros(()))
            """,
        "engine.py": """
            import threading
            from pool import SlotPool

            class Engine:
                def __init__(self):
                    self.pool = SlotPool()
                    self._thread = threading.Thread(target=self._loop,
                                                    daemon=True)
                    self._thread.start()

                def _loop(self):
                    while True:
                        self.pool.step()

                def last(self):
                    # a host-only read never touches the dispatch path
                    return self.pool.last
            """,
    }, select=["foreign-thread-device-access"])
    assert findings == []


# ----------------------------------------------------- lock-across-dispatch

def test_lock_across_dispatch_fires_through_helper(tmp_path):
    """Interprocedural: the blocking device readback happens in a
    helper called while the lock is held."""
    findings = lint_project(tmp_path, {
        "engine.py": """
            import threading
            import jax

            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._thread = threading.Thread(target=self._loop,
                                                    daemon=True)
                    self._thread.start()

                def _loop(self):
                    pass

                def sync(self, x):
                    with self._lock:
                        return self._pull(x)

                def _pull(self, x):
                    return jax.device_get(x)
            """,
    }, select=["lock-across-dispatch"])
    assert rules_of(findings) == ["lock-across-dispatch"]
    assert "jax.device_get" in findings[0].message


def test_lock_across_dispatch_quiet_after_release(tmp_path):
    findings = lint_project(tmp_path, {
        "engine.py": """
            import threading
            import jax

            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.pending = None
                    self._thread = threading.Thread(target=self._loop,
                                                    daemon=True)
                    self._thread.start()

                def _loop(self):
                    pass

                def sync(self, x):
                    with self._lock:
                        y = self.pending
                    # the blocking readback runs outside the lock
                    return jax.device_get(y if y is not None else x)
            """,
    }, select=["lock-across-dispatch"])
    assert findings == []


def test_sarif_report_shape(tmp_path):
    from bigdl_tpu.lint.reporters import sarif_report

    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax\n\n@jax.jit\ndef f(x):\n"
                     "    return float(x)\n")
    result = lint_paths([str(dirty)], baseline_path=None,
                        root=str(tmp_path))
    doc = json.loads(sarif_report(result))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "jaxlint"
    assert run["results"][0]["ruleId"] == "host-sync-in-jit"
    assert run["results"][0]["baselineState"] == "new"
    assert run["results"][0]["level"] == "error"
    fp = run["results"][0]["partialFingerprints"]["jaxlint/v1"]
    assert fp == result.findings[0].fingerprint
