"""Unit tests for the jaxlint v3 passes: mesh/sharding consistency
(`lint/sharding.py`), Pallas kernel safety (`lint/pallas.py`), and the
flag registry (`lint/flags.py`) — every rule fires on its fixture and
stays quiet on the negative twin, plus ShardingIndex/PallasSite unit
tests, the acceptance corruption scenario against a scratch copy of the
real package, and the v3 CLI surface (--rule, exit-code consistency).
"""

import os
import shutil
import textwrap

from bigdl_tpu.lint import lint_file, lint_paths
from bigdl_tpu.lint.__main__ import main as lint_main
from bigdl_tpu.lint.engine import _build_context
from bigdl_tpu.lint.flags import FlagUndocumented
from bigdl_tpu.lint.pallas import pallas_sites
from bigdl_tpu.lint.project import ProjectIndex
from bigdl_tpu.lint.rules import RULES_BY_NAME
from bigdl_tpu.lint.sharding import ShardingIndex

PACKAGE_DIR = os.path.dirname(
    os.path.abspath(__import__("bigdl_tpu").__file__))


def lint_src(tmp_path, source, select=None, name="fixture.py", root=None):
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    rules = [RULES_BY_NAME[s] for s in select] if select else None
    return lint_file(str(f), rules=rules, root=root)


def lint_tree(tmp_path, files, select=None, rules=None):
    """Write a fixture tree and lint it as one project (root=tmp_path,
    so sanctioned-module suffix matching sees real relpaths)."""
    paths = []
    for name, source in files.items():
        f = tmp_path / name
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent(source))
        paths.append(str(f))
    if rules is None and select:
        rules = [RULES_BY_NAME[s] for s in select]
    result = lint_paths(paths, rules=rules, baseline_path=None,
                        root=str(tmp_path))
    assert result.errors == []
    return result.findings


def build_project(tmp_path, files):
    ctxs = []
    for name, source in files.items():
        f = tmp_path / name
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent(source))
        ctx, findings = _build_context(str(f), str(tmp_path))
        assert ctx is not None and findings == []
        ctxs.append(ctx)
    return ProjectIndex(ctxs)


def rules_of(findings):
    return sorted(f.rule for f in findings)


# ------------------------------------------------------- ShardingIndex --

def test_sharding_index_collects_all_declaration_sources(tmp_path):
    project = build_project(tmp_path, {
        "layout.py": """
            from jax.sharding import Mesh

            class SpecLayout:
                data_axis: str = "data"
                tp_axis: str = "tp"

            def build(devs, axis_name="seq"):
                axes = {"pipe": 2}
                return Mesh(devs, ("fsdp", "tp"))
            """,
    })
    shx = ShardingIndex(project)
    assert set(shx.declared) == {"data", "tp", "fsdp", "seq", "pipe"}
    # axis fields resolve attribute references symbolically
    assert shx.axis_fields == {"data_axis": "data", "tp_axis": "tp"}


def test_sharding_index_axis_value_resolution(tmp_path):
    import ast as _ast
    project = build_project(tmp_path, {
        "m.py": """
            class L:
                tp_axis: str = "tp"
            """,
    })
    shx = ShardingIndex(project)
    const = _ast.parse('"data"', mode="eval").body
    attr = _ast.parse("spec.tp_axis", mode="eval").body
    name = _ast.parse("ax", mode="eval").body
    assert shx.axis_value(const) == "data"
    assert shx.axis_value(attr) == "tp"
    assert shx.axis_value(name, {"ax": "fsdp"}) == "fsdp"
    assert shx.axis_value(name, {}) is None  # unresolvable, never guessed


# ------------------------------------------------- spec-axis-not-in-mesh --

def test_spec_axis_typo_fires(tmp_path):
    findings = lint_src(tmp_path, """
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(devs, ("data", "tp"))

        def kv_pool():
            return P(None, "tpp", None, None)   # transposed letters
        """, select=["spec-axis-not-in-mesh"])
    assert len(findings) == 1
    assert "'tpp'" in findings[0].message


def test_spec_axis_quiet_on_declared_and_unresolvable(tmp_path):
    findings = lint_src(tmp_path, """
        from jax.sharding import Mesh, PartitionSpec as P

        class SpecLayout:
            tp_axis: str = "tp"

        mesh = Mesh(devs, ("data", "tp"))

        def specs(spec, axis="seq", dyn=None):
            ax = "data"
            return (P("data", "tp"),        # declared by the mesh
                    P(spec.tp_axis),        # axis-field attribute
                    P(axis),                # param default declares it
                    P(ax),                  # local constant binding
                    P(None, ("data", "tp")),  # tuple entry form
                    P(dyn))                 # unresolvable: skipped
        """, select=["spec-axis-not-in-mesh"])
    assert findings == []


# --------------------------------------------- collective-axis-undeclared --

def test_collective_axis_fires_on_undeclared_names(tmp_path):
    findings = lint_src(tmp_path, """
        import jax
        from jax.sharding import Mesh

        mesh = Mesh(devs, ("data", "tp"))

        def body(x):
            y = jax.lax.psum(x, "ring")          # nothing declares 'ring'
            i = jax.lax.axis_index("nope")       # axis at position 0
            return y, i
        """, select=["collective-axis-undeclared"])
    assert len(findings) == 2
    assert "'ring'" in findings[0].message
    assert "'nope'" in findings[1].message


def test_collective_axis_quiet_on_declared_and_parameterized(tmp_path):
    findings = lint_src(tmp_path, """
        import jax
        from jax.sharding import Mesh

        mesh = Mesh(devs, ("data", "tp"))

        def body(x, axis_name="data", dyn=None):
            a = jax.lax.psum(x, "tp")
            b = jax.lax.pmean(x, axis_name=("data", "tp"))
            c = jax.lax.psum(x, axis_name)   # param default declares it
            d = jax.lax.psum(x, dyn)         # unresolvable: skipped
            return a + b + c + d
        """, select=["collective-axis-undeclared"])
    assert findings == []


# ------------------------------------------------- shardmap-spec-mismatch --

def test_shardmap_spec_count_mismatch_fires(tmp_path):
    findings = lint_src(tmp_path, """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def body(a, b):
            return a + b

        f = shard_map(body, mesh=m, in_specs=(P(), P(), P()),
                      out_specs=P())
        """, select=["shardmap-spec-mismatch"])
    assert len(findings) == 1
    assert "3 spec(s)" in findings[0].message
    assert "body()" in findings[0].message


def test_shardmap_spec_quiet_on_match_partial_and_prefix(tmp_path):
    findings = lint_src(tmp_path, """
        import functools
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def body(a, b, c=None):
            return a

        def wide(cfg, a, b):
            return a

        f = shard_map(body, mesh=m, in_specs=(P(), P()),   # 2 in 2..3
                      out_specs=P())
        g = shard_map(body, mesh=m, in_specs=(P(), P(), P()),  # default used
                      out_specs=P())
        h = shard_map(functools.partial(wide, cfg), mesh=m,  # 1 bound
                      in_specs=(P(), P()), out_specs=P())
        k = shard_map(body, mesh=m, in_specs=P(),  # pytree prefix: skipped
                      out_specs=P())
        """, select=["shardmap-spec-mismatch"])
    assert findings == []


# ----------------------------------------------- jit-missing-out-shardings --

def test_jit_missing_out_shardings_fires(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        step = jax.jit(fn, in_shardings=(spec,))
        """, select=["jit-missing-out-shardings"])
    assert len(findings) == 1


def test_jit_out_shardings_present_or_absent_inputs_quiet(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        a = jax.jit(fn, in_shardings=(spec,), out_shardings=spec)
        b = jax.jit(fn)                      # no sharded inputs: fine
        c = jax.jit(fn, donate_argnums=(0,))
        """, select=["jit-missing-out-shardings"])
    assert findings == []


# ------------------------------------------------------- silent-replicate --

def test_silent_replicate_fires_without_marker(tmp_path):
    findings = lint_src(tmp_path, """
        def plane(layout, spec, shape):
            return layout.sharding(spec, shape)

        class Slots:
            def plane(self, spec, shape):
                return self.layout.fit(spec, shape)
        """, select=["silent-replicate"])
    assert len(findings) == 2
    assert all("allow_replicate" in f.message for f in findings)


def test_silent_replicate_quiet_with_marker_or_off_pattern(tmp_path):
    findings = lint_src(tmp_path, """
        def plane(layout, model, spec, shape):
            a = layout.sharding(spec, shape, allow_replicate=False)
            b = layout.fit(spec, shape=shape, allow_replicate=True)
            c = layout.spec()                  # not fit/sharding
            d = layout.fit(spec)               # no shape: no fallback
            e = model.fit(x, y)                # keras-style: not a layout
            return a, b, c, d, e

        class ModelLayout:
            def sharding(self, spec, shape):
                return self.fit(spec, shape)   # the layout's own helper
        """, select=["silent-replicate"])
    assert findings == []


# ------------------------------------------------------------ PallasSite --

PALLAS_PREFETCH_MODULE = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(tbl, x_ref, o_ref, acc_ref):
        acc_ref[...] = jnp.zeros((8, 128), jnp.float32)
        acc_ref[...] += x_ref[...]
        o_ref[...] = acc_ref[...]

    def call(x, interpret=False):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(4, 2),
            in_specs=[pl.BlockSpec((8, 128), lambda i, j, tbl: (i, j))],
            out_specs=pl.BlockSpec((8, 128), lambda i, j, tbl: (i, j)),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
        )
        return pl.pallas_call(kernel, grid_spec=grid_spec,
                              interpret=interpret)(x)
    """


def test_pallas_site_resolves_prefetch_grid_spec(tmp_path):
    project = build_project(tmp_path, {
        "kern.py": PALLAS_PREFETCH_MODULE,
    })
    ctx = project.modules[0]
    sites = pallas_sites(ctx)
    assert len(sites) == 1
    site = sites[0]
    assert site.grid_rank == 2
    assert site.num_prefetch == 1
    assert len(site.in_specs) == 1 and len(site.out_specs) == 1
    assert site.has_interpret
    assert site.kernel is not None and site.kernel.name == "kernel"
    assert len(site.scratch) == 1
    shape_elts, dtype, _node = site.scratch[0]
    assert len(shape_elts) == 2 and dtype == "float32"
    params, rank = site.map_arity(site.in_specs[0], ctx.index)
    assert params == 3 and rank == 2  # 2 grid + 1 prefetch; 2-tuple out


def test_pallas_prefetch_module_is_rule_clean(tmp_path):
    findings = lint_src(
        tmp_path, PALLAS_PREFETCH_MODULE,
        select=["pallas-blockspec-arity", "pallas-prefetch-arity",
                "pallas-scratch-uninit", "pallas-vmem-budget",
                "pallas-missing-interpret"])
    assert findings == []


# ------------------------------------------------- pallas-blockspec-arity --

def test_blockspec_arity_fires_on_both_contracts(tmp_path):
    findings = lint_src(tmp_path, """
        from jax.experimental import pallas as pl

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4, 4),
                in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((128,), lambda i, j: (i, j)),
                interpret=True)(x)
        """, select=["pallas-blockspec-arity"])
    assert len(findings) == 2
    assert "1 argument(s)" in findings[0].message      # map vs grid rank 2
    assert "rank 1" in findings[1].message             # block vs 2-tuple map


def test_blockspec_arity_quiet_on_named_maps_and_bare_grid(tmp_path):
    findings = lint_src(tmp_path, """
        from jax.experimental import pallas as pl

        def q_map(i, j):
            return (i, j)

        def call(x):
            a = pl.pallas_call(
                kernel,
                grid=(4, 4),
                in_specs=[pl.BlockSpec((128, 128), q_map)],
                out_specs=pl.BlockSpec((128, 128), lambda i, j: (i, j)),
                interpret=True)(x)
            b = pl.pallas_call(             # bare int grid is rank 1
                kernel,
                grid=4,
                in_specs=[pl.BlockSpec((128,), lambda i: (i,))],
                out_specs=pl.BlockSpec((128,), lambda i: (i,)),
                interpret=True)(x)
            return a, b
        """, select=["pallas-blockspec-arity"])
    assert findings == []


# -------------------------------------------------- pallas-prefetch-arity --

def test_prefetch_arity_fires_on_bare_grid_map(tmp_path):
    findings = lint_src(tmp_path, """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def call(x):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(4,),
                in_specs=[pl.BlockSpec((128,), lambda i: (i,))],
                out_specs=pl.BlockSpec((128,), lambda i, t, s: (i,)),
            )
            return pl.pallas_call(kernel, grid_spec=grid_spec,
                                  interpret=True)(x)
        """, select=["pallas-prefetch-arity"])
    assert len(findings) == 1
    assert "1 grid index(es) + 2 scalar-prefetch ref(s) = 3" \
        in findings[0].message


def test_prefetch_arity_quiet_when_maps_take_the_refs(tmp_path):
    findings = lint_src(tmp_path, """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def call(x):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(4,),
                in_specs=[pl.BlockSpec((128,), lambda i, t, s: (i,))],
                out_specs=pl.BlockSpec((128,), lambda i, t, s: (i,)),
            )
            return pl.pallas_call(kernel, grid_spec=grid_spec,
                                  interpret=True)(x)
        """, select=["pallas-prefetch-arity"])
    assert findings == []


# -------------------------------------------------- pallas-scratch-uninit --

def test_scratch_read_before_init_fires(tmp_path):
    findings = lint_src(tmp_path, """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_ref, o_ref, acc_ref):
            o_ref[...] = acc_ref[...] + x_ref[...]   # acc is garbage here

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((128,), lambda i: (i,))],
                out_specs=pl.BlockSpec((128,), lambda i: (i,)),
                scratch_shapes=[pltpu.VMEM((128,), jnp.float32)],
                interpret=True)(x)
        """, select=["pallas-scratch-uninit"])
    assert len(findings) == 1
    assert "'acc_ref'" in findings[0].message


def test_scratch_guarded_init_idiom_quiet(tmp_path):
    findings = lint_src(tmp_path, """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_ref, o_ref, acc_ref):
            @pl.when(pl.program_id(0) == 0)
            def _init():
                acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
            acc_ref[...] += x_ref[...]       # augmented fold after init
            o_ref[...] = acc_ref[...]

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((128,), lambda i: (i,))],
                out_specs=pl.BlockSpec((128,), lambda i: (i,)),
                scratch_shapes=[pltpu.VMEM((128,), jnp.float32)],
                interpret=True)(x)
        """, select=["pallas-scratch-uninit"])
    assert findings == []


# ---------------------------------------------------- pallas-vmem-budget --

def test_vmem_budget_fires_on_oversized_blocks(tmp_path):
    findings = lint_src(tmp_path, """
        from jax.experimental import pallas as pl

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4, 4),
                in_specs=[pl.BlockSpec((2048, 2048),
                                       lambda i, j: (i, j))],
                out_specs=pl.BlockSpec((2048, 2048),
                                       lambda i, j: (i, j)),
                interpret=True)(x)
        """, select=["pallas-vmem-budget"])
    assert len(findings) == 1
    assert "MiB" in findings[0].message


def test_vmem_budget_counts_scratch_and_stays_quiet_small(tmp_path):
    fire = lint_src(tmp_path, """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((128,), lambda i: (i,))],
                out_specs=pl.BlockSpec((128,), lambda i: (i,)),
                scratch_shapes=[pltpu.VMEM((2048, 2048), jnp.float32)],
                interpret=True)(x)
        """, select=["pallas-vmem-budget"], name="scratch_heavy.py")
    assert len(fire) == 1  # 16 MiB of f32 scratch alone blows 75%

    quiet = lint_src(tmp_path, """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),
                scratch_shapes=[pltpu.VMEM((2048, 2048), jnp.bfloat16)],
                interpret=True)(x)
        """, select=["pallas-vmem-budget"], name="scratch_bf16.py")
    assert quiet == []  # bf16 halves the scratch term: 8 MiB < 12 MiB


# ------------------------------------------------ pallas-missing-interpret --

def test_missing_interpret_fires_and_gated_quiet(tmp_path):
    fire = lint_src(tmp_path, """
        from jax.experimental import pallas as pl

        def call(x):
            return pl.pallas_call(kernel, grid=(4,))(x)
        """, select=["pallas-missing-interpret"], name="bare.py")
    assert rules_of(fire) == ["pallas-missing-interpret"]

    quiet = lint_src(tmp_path, """
        from jax.experimental import pallas as pl
        from bigdl_tpu.ops.pallas_util import use_interpret

        def call(x):
            return pl.pallas_call(kernel, grid=(4,),
                                  interpret=use_interpret())(x)
        """, select=["pallas-missing-interpret"], name="gated.py")
    assert quiet == []


# ------------------------------------------------------- flag-unregistered --

ENGINE_FIXTURE = """
    # Flag registry:
    #   BIGDL_TPU_PLATFORM     force the jax platform
    #   BIGDL_TPU_GOOD_KNOB    a registered knob
    import os

    def get_flag(name, default=None):
        return os.environ.get(name, default)
    """


def test_flag_unregistered_fires_on_missing_registry_entry(tmp_path):
    findings = lint_tree(tmp_path, {
        "utils/engine.py": ENGINE_FIXTURE,
        "train.py": """
            from bigdl_tpu.utils.engine import get_flag

            good = get_flag("BIGDL_TPU_GOOD_KNOB")
            bad = get_flag("BIGDL_TPU_ROGUE_KNOB")
            """,
    }, select=["flag-unregistered"])
    assert len(findings) == 1
    assert "BIGDL_TPU_ROGUE_KNOB" in findings[0].message
    assert findings[0].path == "train.py"


def test_flag_unregistered_skips_without_registry_module(tmp_path):
    findings = lint_src(tmp_path, """
        def setup(get_flag):
            return get_flag("BIGDL_TPU_NOT_SEEN")
        """, select=["flag-unregistered"])
    assert findings == []  # single-file run can't see the registry


# ------------------------------------------------------- flag-undocumented --

def test_flag_undocumented_fires_against_doc_catalog(tmp_path):
    doc = tmp_path / "docs" / "configuration.md"
    doc.parent.mkdir(parents=True)
    doc.write_text("| `BIGDL_TPU_GOOD_KNOB` | documented |\n")
    rule = FlagUndocumented()
    rule.doc_path = str(doc)
    findings = lint_tree(tmp_path, {
        "train.py": """
            from bigdl_tpu.utils.engine import get_flag

            good = get_flag("BIGDL_TPU_GOOD_KNOB")
            bad = get_flag("BIGDL_TPU_SECRET_KNOB")
            """,
    }, rules=[rule])
    assert len(findings) == 1
    assert "BIGDL_TPU_SECRET_KNOB" in findings[0].message


def test_flag_undocumented_skips_without_doc_file(tmp_path):
    rule = FlagUndocumented()
    rule.doc_path = str(tmp_path / "missing" / "configuration.md")
    findings = lint_tree(tmp_path, {
        "train.py": """
            from bigdl_tpu.utils.engine import get_flag

            x = get_flag("BIGDL_TPU_WHATEVER")
            """,
    }, rules=[rule])
    assert findings == []


# -------------------------------------------------------- raw-environ-read --

RAW_ENV_SOURCE = """
    import os

    home = os.environ["HOME"]
    opt = os.environ.get("MY_OPT")
    alt = os.getenv("MY_ALT", "0")
    has = "MY_KEY" in os.environ
    os.environ["CHILD_VAR"] = "1"   # a write, not a read: quiet
    """


def test_raw_environ_read_fires_outside_sanctioned_modules(tmp_path):
    findings = lint_src(tmp_path, RAW_ENV_SOURCE,
                        select=["raw-environ-read"], name="train.py",
                        root=str(tmp_path))
    assert len(findings) == 4  # subscript, .get, getenv, `in` — not the set


def test_raw_environ_read_quiet_in_sanctioned_modules(tmp_path):
    for name in ("utils/engine.py", "resilience/faults.py",
                 "launcher.py", "utils/compile_cache.py",
                 "mytool/lint/probe.py"):
        findings = lint_src(tmp_path, RAW_ENV_SOURCE,
                            select=["raw-environ-read"], name=name,
                            root=str(tmp_path))
        assert findings == [], name


# ------------------------------------------------- acceptance: corruption --

def test_corrupted_scratch_copy_yields_exactly_the_two_findings(tmp_path):
    """The ISSUE acceptance scenario: corrupt one SpecLayout axis name
    and one BlockSpec arity in a scratch copy of the real package; the
    v3 passes must report exactly those two findings."""
    copy = tmp_path / "bigdl_tpu"
    shutil.copytree(PACKAGE_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))

    layout = copy / "parallel" / "layout.py"
    src = layout.read_text()
    assert 'return P(None, self.tp_axis, None, None)' in src
    layout.write_text(src.replace(
        'return P(None, self.tp_axis, None, None)',
        'return P(None, "tpp", None, None)', 1))

    kernel = copy / "ops" / "paged_attention.py"
    src = kernel.read_text()
    assert 'pl.BlockSpec((None, hb, c, d), q_map),' in src
    kernel.write_text(src.replace(
        'pl.BlockSpec((None, hb, c, d), q_map),',
        'pl.BlockSpec((None, hb, c), q_map),', 1))

    result = lint_paths([str(copy)], baseline_path=None,
                        root=str(tmp_path))
    assert result.errors == []
    assert rules_of(result.findings) == ["pallas-blockspec-arity",
                                         "spec-axis-not-in-mesh"]
    by_rule = {f.rule: f for f in result.findings}
    assert "'tpp'" in by_rule["spec-axis-not-in-mesh"].message
    assert "rank 3" in by_rule["pallas-blockspec-arity"].message


# ------------------------------------------------------------ CLI surface --

FIRE_SOURCE = """
    def plane(layout, spec, shape):
        return layout.sharding(spec, shape)
    """


def write_fixture(tmp_path, source, name="cli_fixture.py"):
    f = tmp_path / name
    f.write_text(textwrap.dedent(source))
    return str(f)


def test_cli_rule_filter_selects_one_rule(tmp_path, capsys):
    path = write_fixture(tmp_path, FIRE_SOURCE)
    rc = lint_main(["--rule", "silent-replicate", "--no-baseline", path])
    out = capsys.readouterr().out
    assert rc == 1
    assert "silent-replicate" in out
    # the same file is clean under an unrelated rule
    rc = lint_main(["--rule", "pallas-vmem-budget", "--no-baseline", path])
    assert rc == 0


def test_cli_rule_combines_with_select_and_rejects_unknown(tmp_path,
                                                           capsys):
    path = write_fixture(tmp_path, FIRE_SOURCE)
    rc = lint_main(["--select", "key-reuse", "--rule", "silent-replicate",
                    "--no-baseline", path])
    assert rc == 1
    rc = lint_main(["--rule", "no-such-rule", "--no-baseline", path])
    assert rc == 2
    assert "no-such-rule" in capsys.readouterr().err


def test_cli_exit_code_is_reporter_independent(tmp_path, capsys):
    dirty = write_fixture(tmp_path, FIRE_SOURCE, "dirty.py")
    clean = write_fixture(tmp_path, "x = 1\n", "clean.py")
    for fmt in ("text", "json", "sarif"):
        rc = lint_main(["--format", fmt, "--no-baseline", dirty])
        capsys.readouterr()
        assert rc == 1, fmt
        rc = lint_main(["--format", fmt, "--no-baseline", clean])
        capsys.readouterr()
        assert rc == 0, fmt


def test_sarif_rules_carry_help_uris(tmp_path, capsys):
    import json
    dirty = write_fixture(tmp_path, FIRE_SOURCE, "sarif_fix.py")
    rc = lint_main(["--format", "sarif", "--no-baseline", dirty])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    rules = doc["runs"][0]["tool"]["driver"]["rules"]
    assert any(r["id"] == "silent-replicate" for r in rules)
    for r in rules:
        assert r["helpUri"] == f"docs/linting.md#{r['id']}"
