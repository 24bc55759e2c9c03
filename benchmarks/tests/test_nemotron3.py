"""The Nemotron-3-Super configuration's own arithmetic and readers, on the CPU: the counts against the numbers worked out by hand and
against the program's tree, the configuration file against the catalog's published keys it repeats, the four new readers on hand-made
spans and a hand-made trace, the reference's independence of the program, and the cell's rehearsal. Where a metric set is checked it is
held to "at least these": a later PR may append its own."""
import json
import os

import pytest

from benchmarks.harness import counts_nemotron3 as counts
from benchmarks.harness import peaks, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "nemotron3-reason-closed"
CONFIG = "nemotron3-super-ep8-serve"
NEW = ["ssm_step_ms", "ssm_step_roofline", "decode_step_roofline.nemotron3", "mfu.serve.nemotron3"]
JOINED = ["tpot_p95_ms", "slot_occupancy_mean", "decode_step_ms", "device_idle_share.serve", "window_compiles", "step_host_ms",
          "moe_experts_hit_mean", "token_gaps_after_prefill_share", "deliver_to_client_ms", "prefill_ms.batch"]
REDUCED = {"num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"}


def _run_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run():
    return _run_module()


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ counts --
def test_param_count_is_the_deployments_arithmetic(config):
    s = counts.shape(config)
    # the deployment's arithmetic: a Mamba layer 109.64 M, attention 35.66 M (its norm beside), the MoE outside its experts 54.53 M, an
    # expert 5.505 M, this chip 2752 M = 5.50 GB in bfloat16
    assert round(counts.mamba_params(s) / 1e4) == 10964
    assert counts.mamba_matrix_params(s) == 4096 * (8192 + 10240 + 128) + 8192 * 4096
    assert round((counts.attention_params(s) + 4096) / 1e4) == 3566
    assert round((counts.moe_outside_params(s) + 512) / 1e4) == 5453
    assert counts.expert_params(s) == 2 * 1024 * 2688 == 5_505_024
    assert round(counts.param_count(s) / 1e6) == 2752 and round(2 * counts.param_count(s) / 1e9, 2) == 5.50
    # "about 78 M parameters a layer beside its experts", the catalog's count over one period
    per_layer = (5 * counts.mamba_params(s) + counts.attention_params(s) + 5 * counts.moe_outside_params(s)) / 11
    assert round(per_layer / 1e6) == 78


def test_param_count_is_the_programs_tree(config):
    import jax
    from benchmarks.harness import resolve
    model = resolve(config["constructor"])(**config["constructor_kwargs"])
    tree = jax.eval_shape(lambda k: model.setup(k, None)[0], jax.random.key(0))
    assert sum(int(l.size) for l in jax.tree_util.tree_leaves(tree)) == counts.param_count(counts.shape(config))
    cache = jax.eval_shape(lambda: model.init_cache(config["engine_kwargs"]["max_slots"], "bfloat16"))
    held = sum(int(l.size) * l.dtype.itemsize for l in jax.tree_util.tree_leaves(cache))
    # S float32 4.19 MB and the taps 0.06 MB a slot a Mamba layer, K and V 1 KB a position: 2.72 + 0.81 GB for 128 slots
    s = counts.shape(config)
    per_slot = 5 * (4 * counts.state_numbers(s) + 2 * 3 * s["conv_dim"]) + 6144 * counts.kv_row_bytes(s, 2)
    assert held == 128 * per_slot and round(held / 1e9, 2) == 3.53


def test_the_file_repeats_the_published_keys_and_cuts_depth_pattern_experts_and_vocabulary(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG)
    changed = {k for k, v in config["published"].items() if config[k] != v}
    assert changed == set(entry["reduced"]) == REDUCED
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (11, 64, 16384)
    assert config["hybrid_override_pattern"] == config["published"]["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    kw = config["constructor_kwargs"]
    for key, value in config["published"].items():
        if key in kw and key not in REDUCED:
            assert kw[key] == value, key
    # the router keeps its published width and its 22 a token; 64 of the 512 are held
    assert (kw["n_routed_experts"], kw["experts_held"], kw["num_experts_per_tok"]) == (512, 64, 22)
    assert (kw["vocab_size"], kw["max_position"]) == (16384, 6144)
    assert config["engine_kwargs"]["max_slots"] == 128 and config["engine_kwargs"]["max_queue"] == 256
    assert entry["source"] == config["source"] and entry["file"] == "benchmarks/configs/" + CONFIG + ".json"
    for key in ("reduced", "deployment", "assumed"):
        assert config[key]
    # the published sizes the widths come from, as the source's config.json states them
    published = config["published"]
    assert published["model_type"] == "nemotron_h" and len(published) == 50
    assert {k: published[k] for k in ("hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
                                      "chunk_size", "num_attention_heads", "num_key_value_heads", "head_dim", "n_routed_experts",
                                      "num_experts_per_tok", "moe_intermediate_size", "moe_latent_size",
                                      "moe_shared_expert_intermediate_size", "routed_scaling_factor", "vocab_size",
                                      "num_hidden_layers")} == {
        "hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
        "chunk_size": 128, "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128, "n_routed_experts": 512,
        "num_experts_per_tok": 22, "moe_intermediate_size": 2688, "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
        "routed_scaling_factor": 5, "vocab_size": 131072, "num_hidden_layers": 88}


def test_a_step_needs_the_state_the_weights_hit_and_the_context():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        s = counts.shape(json.load(f))
    # the least bytes of one step with 128 live streams: non-expert weights 1.85 GB, 63.7 experts hit 3.51 GB, S read and
    # written 5.37 GB, K/V at the traffic's mean context 0.24 GB
    flops, nbytes = counts.decode_step_need(s, 128, 128 * 1900, 63.7, 2, 2)
    weights = (counts.non_expert_matrix_params(s) + 4096 * 16384) * 2
    assert round(weights / 1e9, 2) == 1.85
    assert round(5 * 63.7 * counts.expert_params(s) * 2 / 1e9, 2) == 3.51
    assert round(5 * 128 * 2 * 4 * counts.state_numbers(s) / 1e9, 2) == 5.37
    assert round(128 * 1900 * counts.kv_row_bytes(s, 2) / 1e9, 2) == 0.25
    assert 10.9e9 < nbytes < 11.2e9
    p = peaks.peaks_for("TPU v5 lite")
    assert nbytes / p["bytes_per_s"] > 13e-3 and flops / p["flops_per_s"] < 4e-3           # bound by bytes
    # one more row of context is one more row of K and V read, nothing more
    more = counts.decode_step_need(s, 128, 128 * 1900 + 1, 63.7, 2, 2)
    assert (more[0] - flops, round(more[1] - nbytes)) == (4 * 32 * 128, 1024)


def test_the_state_kernels_need_is_the_state_moved():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        s = counts.shape(json.load(f))
    flops, nbytes = counts.ssm_step_need(s, 128)
    state = 5 * 128 * 2 * 4 * 128 * 64 * 128
    rows = 5 * 128 * 4 * (2 * 8192 + 2 * 1024 + 128)
    assert nbytes == state + rows and rows < 0.02 * state
    assert flops == 128 * 5 * 5 * 128 * 64 * 128


@pytest.mark.parametrize("n", [1, 127, 128, 129, 768])
def test_a_prefill_is_the_sum_of_its_positions(config, n):
    s = counts.shape(config)
    by_position = sum(counts.context_flops(s) * (p + 1) for p in range(n))
    assert counts.prefill_flops(s, n) == n * (counts.token_matrix_flops(s) + counts.recurrence_flops(s)) + by_position \
        + counts.head_flops(s)
    assert counts.decode_flops(s, n) - counts.decode_flops(s, n - 1) == counts.context_flops(s)


# ----------------------------------------------------------------- readers --
STEP = ("jit_step(7)", "jit_prefill(8)")


def _trace(ssm_ms=1.6):
    """Two launches of the step (25 ms each, five state kernels of ``ssm_ms`` / 5 ms in each) and one prefill."""
    ops = []
    for at in (0.000, 0.030):
        ops.append(["%fusion.1 = f32[128,4096]{1,0} fusion(...)", at, 0.010])
        for layer in range(5):
            ops.append([f"%ssm_step.{layer} = f32[128,128,64,128]{{3,2,1,0:T(8,128)}} custom-call(s32[128]{{0}} %a, s32[1]{{0}} %b)",
                        at + 0.010 + 0.002 * layer, ssm_ms * 1e-3 / 5])
    ops.append(["%ssm_step.9 = f32[1,128,64,128] custom-call(...)", 0.060, 0.001])
    return trace_reduce.Reduced({"0": ops}, {"0": [[STEP[0], 0.0, 0.025], [STEP[0], 0.030, 0.025], [STEP[1], 0.058, 0.040]]}, [])


def _spans(with_state=True):
    base = {"live": 128, "kv_write": "scatter", "attn_read": "masked", "experts": "gmm", "assignments": 2816, "experts_hit": 63.5,
            "assignments_held": 352.0}
    first = {"ssm_slots": 128, "ssm_state_bytes": 5_368_709_120, "attn_rows": 128 * 1900} if with_state else {}
    second = {"ssm_slots": 126, "ssm_state_bytes": 5_284_823_040, "attn_rows": 126 * 1900} if with_state else {}
    return [("serve/step", 10.0, 10.025, dict(base, iter=1, **first)),
            ("serve/step", 10.03, 10.055, dict(base, iter=2, live=126, **second)),
            ("serve/prefill", 10.058, 10.098, dict({"iter": 3, "n": 1, "rows": 1, "bucket": 1024, "tokens": 768},
                                                   **({"ssm_chunks": 6, "ssm_positions": 768} if with_state else {})))]


class _Planned:
    def __init__(self, n):
        self.prompt = [0] * n


class _Record:
    def __init__(self, n0, token_at):
        self.planned, self.token_at = _Planned(n0), token_at


def _ctx(run, config, spans=(), trace=None, records=(), traced=(10.0, 10.1)):
    return run.Ctx(config=config, spans=list(spans), trace=trace, records=list(records), traced=traced, chips=1,
                   peaks=peaks.peaks_for("TPU v5 lite"))


def test_ssm_step_ms_sums_the_kernels_inside_the_step_launches(run, config):
    ctx = _ctx(run, config, _spans(), _trace(1.6))
    assert run.load_reader(BENCH, "ssm_step_ms")(ctx) == pytest.approx(1.6)           # the prefill's call is not a step's
    assert config["ssm_op"] == "ssm_step"
    assert run.load_reader(BENCH, "ssm_step_ms")(_ctx(run, dict(config, ssm_op=None), _spans(), _trace())) is None


def test_the_kernels_roofline_counts_the_state_of_the_live_slots(run, config):
    ctx = _ctx(run, config, _spans(), _trace(8.0))
    value = run.load_reader(BENCH, "ssm_step_roofline")(ctx)
    _, nbytes = counts.ssm_step_need(counts.shape(config), 127)
    assert value == pytest.approx(100.0 * (nbytes / 819e9) / 8e-3) and value < 100.0
    assert ctx.notes["ssm_step_least_ms"] == pytest.approx(1e3 * nbytes / 819e9)


def test_decode_step_roofline_reads_the_state_and_rows_of_the_step_spans(run, config):
    ctx = _ctx(run, config, _spans(), _trace())
    value = run.load_reader(BENCH, "decode_step_roofline.nemotron3")(ctx)
    _, nbytes = counts.decode_step_need(counts.shape(config), 127, 127 * 1900, 63.5, 2, 2)
    assert value == pytest.approx(100.0 * (nbytes / 819e9) / 25e-3) and value < 100.0
    assert ctx.notes["decode_roofline_bound"] == "bytes"


def test_mfu_counts_prompts_and_tokens_that_reached_a_client(run, config):
    s = counts.shape(config)
    records = [_Record(700, [10.01, 10.05, 10.2]), _Record(3000, [9.0, 10.02])]      # one first token and two later ones inside
    ctx = _ctx(run, config, [], _trace(), records)
    flops = counts.prefill_flops(s, 700) + counts.decode_flops(s, 700) + counts.decode_flops(s, 3000)
    assert run.load_reader(BENCH, "mfu.serve.nemotron3")(ctx) == pytest.approx(100.0 * flops / (0.1 * 197e12))


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("what", ["no span, no trace", "a commit without these spans", "an empty trace"])
def test_where_there_is_nothing_to_read_a_reader_returns_none(run, config, name, what):
    if what == "no span, no trace":
        ctx = _ctx(run, config, traced=None)
    elif what == "a commit without these spans":            # serve/step without the ssm_* attributes: what the parent would leave
        ctx = _ctx(run, config, _spans(False), trace_reduce.Reduced(
            {"0": [["%fusion.1 = f32[128,4096]{1,0} fusion(...)", 0.0, 0.01]]}, {"0": [[STEP[0], 0.0, 0.025]]}, []))
    else:
        ctx = _ctx(run, config, _spans(False), trace_reduce.Reduced({}, {}, []))
    value = run.load_reader(BENCH, name)(ctx)
    # the whole step's share counts the model's arithmetic from the clients' records: with a trace it reads 0, not None
    assert value is None or (name == "mfu.serve.nemotron3" and value == 0.0)
    json.dumps(ctx.notes)


def test_every_new_metric_is_an_entry_a_file_and_lists_only_the_cell(run):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in names if n in NEW] == NEW
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, CELL, 1) and len(cell["why"]) <= 200
    reported = {m["name"] for m in run.metrics_for(manifest, cell, "per_layer")}
    assert set(NEW) | set(JOINED[1:]) <= reported
    assert "idle_named_share.serve" not in reported
    assert {"tpot_p95_ms", "setup_s"} <= {m["name"] for m in run.metrics_for(manifest, cell, "end_to_end")}
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p95_ms" and callable(run.load_reader(BENCH, m["name"]))
            assert m["layer"] == ("Whole step" if m["name"].startswith("mfu") else "Kernels")


def test_the_traffic_is_the_cells(config):
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], config["engine_kwargs"]["max_slots"]) == ("closed", 136, 128)
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 768, "sigma": 0.6, "min": 128, "max": 4096}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 768, "sigma": 0.4, "min": 256, "max": 1536}
    assert traffic["sampled_share"] == 0.0 and traffic["temperature"] == 0.0 and traffic["lead_in_s"] >= 20


# --------------------------------------------------------------- reference --
def test_the_reference_imports_nothing_of_the_program_and_walks_the_recurrence():
    with open(os.path.join(BENCH, "reference", "nemotron3.py")) as f:
        text = f.read()
    assert "bigdl_tpu" not in text and "import jax" in text
    assert "scan_chunks" not in text and "jax.lax.scan(one" in text


def test_the_controls_are_the_stated_precision_the_step_below_and_two_planted_faults(config):
    from benchmarks.reference import nemotron3
    _, controls = nemotron3.make(dict(config, constructor_kwargs=config["rehearse"]["constructor_kwargs"]))
    assert set(controls) == {"operands:bfloat16", "operands:float8_e4m3fn", "fault:ssm_no_state", "fault:latent_unscaled"}
    assert config["control"] == "operands:float8_e4m3fn"
    with pytest.raises(ValueError):
        nemotron3.make(dict(config, faults=["no_such_fault"]))


def test_rehearsal_with_a_trace_fills_the_counters_and_no_device_metric(capsys):
    run = _run_module()
    assert run.main(["--workload", CELL, "--seed", str(2**31 + 39), "--seconds", "3", "--trace", "1", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert {"window_compiles"} <= set(out["metrics"]) and out["metrics"]["window_compiles"]["value"] == 0
    assert all(m in ("window_compiles", "token_gaps_after_prefill_share") for m in out["metrics"])
    assert out["notes"]["checked"]["greedy_tokens"] >= 200


def test_a_planted_fault_makes_the_rehearsal_not_correct(capsys):
    run = _run_module()
    assert run.main(["--workload", CELL, "--seed", "7", "--seconds", "3", "--trace", "0", "--rehearse", "--control"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    assert set(out["controls"]) == {"operands:bfloat16", "operands:float8_e4m3fn", "fault:ssm_no_state", "fault:latent_unscaled"}
    for name in ("operands:float8_e4m3fn", "fault:ssm_no_state", "fault:latent_unscaled"):
        assert out["controls"][name]["correct_if_control"] is False
