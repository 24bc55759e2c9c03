"""The LFM2-MoE configuration's own arithmetic and readers, on the CPU: the counts against the parameter count worked out by
hand and against the program's tree, the configuration file against the catalog's published keys it repeats, the four
per-layer readers on hand-made spans and a hand-made trace, and the reference's independence of the program."""
import json
import os

import pytest

from benchmarks.harness import counts_lfm2moe as counts
from benchmarks.harness import peaks, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "lfm2moe-generate-closed"
NEW = ["mfu.serve.lfm2moe", "decode_step_roofline.lfm2moe", "moe_experts_hit_mean", "moe_expert_roofline"]


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
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b-serve.json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ counts --
def test_param_count_is_the_issues_arithmetic(config):
    s = counts.shape(config)
    assert counts.conv_params(s) == 2048 * 6144 + 2048 * 2048 + 2048 * 3                # 16.8 M
    assert counts.attn_params(s) == 2 * 2048 * 2048 + 2 * 2048 * 512 + 128             # 10.5 M and the QK gains
    assert counts.expert_params(s) == 3 * 2048 * 1536                                   # 9.44 M, 18.9 MB in bfloat16
    # 134.2 + 16.8 + 72.4 + 8 x 604.1 + 6 x 16.8 + 2 x 10.5 = 5178 M
    assert round(counts.param_count(s) / 1e6) == 5178
    assert counts.param_count(s) - counts.non_expert_params(s) == 8 * 64 * counts.expert_params(s)


def test_param_count_is_the_programs_tree(config):
    import jax
    from benchmarks.harness import resolve
    model = resolve(config["constructor"])(**config["constructor_kwargs"])
    tree = jax.eval_shape(lambda k: model.setup(k, None)[0], jax.random.key(0))
    n = sum(int(l.size) for l in jax.tree_util.tree_leaves(tree))
    assert n == counts.param_count(counts.shape(config))


def test_the_file_repeats_the_published_keys_and_changes_the_depth_alone(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "lfm2-24b-a2b-serve")
    changed = {k for k, v in config["published"].items() if config[k] != v}
    assert changed == set(entry["reduced"]) == {"num_hidden_layers", "num_dense_layers", "layer_types"}
    kept = [0] + list(range(2, 10))                                                     # published layer 0 and layers 2-9
    assert config["layer_types"] == [config["published"]["layer_types"][i] for i in kept]
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 9
    kw = config["constructor_kwargs"]
    for key in ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "layer_types", "num_dense_layers",
                "num_experts", "num_experts_per_tok", "num_attention_heads", "num_key_value_heads", "conv_L_cache", "norm_eps"):
        assert kw[key] == config[key], key
    assert kw["rope_theta"] == config["rope_parameters"]["rope_theta"]
    assert entry["source"] == config["source"]


def test_a_step_needs_the_experts_hit_and_no_other(config):
    s = counts.shape(config)
    f60, b60 = counts.decode_step_need(s, 96, 40000, 60, 2, 2)
    f64, b64 = counts.decode_step_need(s, 96, 40000, 64, 2, 2)
    assert f60 == f64 and b64 - b60 == 4 * 8 * counts.expert_params(s) * 2
    # every weight, K and V of 40000 + 96 positions at 4 KB, 7 states read and written, 96 rows of float32 logits
    assert b64 == (counts.param_count(s) * 2 + (40000 + 96) * 4096 + 96 * 2 * 7 * 3 * 2048 * 2 + 96 * 65536 * 4)
    ef, eb = counts.expert_step_need(s, 96, 60, 2)
    assert eb == 60 * 8 * 18_874_368 and ef == 8 * 96 * 4 * 2 * counts.expert_params(s)
    # bound by bytes: 12.6 ms for the weights alone against 0.6 ms of arithmetic
    p = peaks.peaks_for("TPU v5 lite")
    assert b64 / p["bytes_per_s"] > 12e-3 and f64 / p["flops_per_s"] < 1e-3


def test_token_flops_count_four_experts_and_the_true_context(config):
    s = counts.shape(config)
    assert counts.decode_flops(s, 101) - counts.decode_flops(s, 100) == 2 * 4 * 32 * 64          # two attention layers
    routed = 2 * 2048 * 64 + 4 * 2 * counts.expert_params(s)
    assert counts.token_flops(s, 0) == (7 * (2 * (2048 * 6144 + 2048 * 2048) + 2 * 3 * 2048)
                                        + 2 * 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 6 * 2048 * 11776 + 8 * routed)
    assert counts.prefill_flops(s, 1) == counts.token_flops(s, 1) + counts.head_flops(s)


# ----------------------------------------------------------------- readers --
STEP = ("jit_step(7)", "jit_prefill(8)")


def _trace(expert_ops=True):
    """Two launches of the step (30 ms each) and one prefill; in each step 16 ms of ``ragged-dot`` custom calls (0.1 ms of it
    their metadata), in the prefill 3 ms that must not count."""
    ops = [["%fusion.1 = f32[96,2048]{1,0} fusion(...)", 0.000, 0.010], ["%fusion.1 = f32[96,2048]{1,0} fusion(...)", 0.040, 0.010]]
    if expert_ops:
        ops += [["%ragged-dot-metadata.2 = (s32[65]) custom-call(...)", 0.0100, 0.0001], ["%ragged-dot-none.3 = f32[384,1536]{1,0} custom-call(...)", 0.0101, 0.0159],
                ["%ragged-dot-metadata.2 = (s32[65]) custom-call(...)", 0.0500, 0.0001], ["%ragged-dot-none.3 = f32[384,1536]{1,0} custom-call(...)", 0.0501, 0.0159],
                ["%ragged-dot-none.9 = f32[8192,1536]{1,0} custom-call(...)", 0.081, 0.003]]
    return trace_reduce.Reduced({"0": ops}, {"0": [[STEP[0], 0.0, 0.030], [STEP[0], 0.040, 0.030], [STEP[1], 0.080, 0.010]]}, [])


def _spans(with_experts=True):
    extra = {"experts": "ragged_dot", "assignments": 384, "experts_hit": 60.0} if with_experts else {}
    return [("serve/step", 10.0, 10.03, dict({"iter": 1, "live": 96, "kv_write": "kernel"}, **extra)),
            ("serve/step", 10.03, 10.06, dict({"iter": 2, "live": 96, "kv_write": "kernel"}, **dict(extra, experts_hit=62.0) if extra else {}))]


class _Planned:
    def __init__(self, n):
        self.prompt = [0] * n


class _Record:
    def __init__(self, n0, token_at):
        self.planned, self.token_at = _Planned(n0), token_at


def _ctx(run, config, spans=(), trace=None, records=(), traced=(10.0, 10.1)):
    return run.Ctx(config=config, spans=list(spans), trace=trace, records=list(records), traced=traced, chips=1,
                   peaks=peaks.peaks_for("TPU v5 lite"))


def test_experts_hit_mean_reads_the_step_spans(run, config):
    assert run.load_reader(BENCH, "moe_experts_hit_mean")(_ctx(run, config, _spans())) == pytest.approx(61.0)


def test_expert_roofline_takes_the_steps_expert_operations_alone(run, config):
    ctx = _ctx(run, config, _spans(), _trace())
    value = run.load_reader(BENCH, "moe_expert_roofline")(ctx)
    _, nbytes = counts.expert_step_need(counts.shape(config), 96, 61.0, 2)
    assert ctx.notes["expert_ms_per_step"] == pytest.approx(16.0)                     # not the prefill's 3 ms
    assert value == pytest.approx(100.0 * (nbytes / 819e9) / 16e-3) and value < 100.0


def test_decode_step_roofline_reads_experts_hit_and_the_live_streams(run, config):
    records = [_Record(100, [10.0 + 0.02 * i for i in range(6)])]                   # one stream, live the whole traced part
    ctx = _ctx(run, config, _spans(), _trace(), records)
    value = run.load_reader(BENCH, "decode_step_roofline.lfm2moe")(ctx)
    live_tokens = sum(0.02 * (100 + i) for i in range(1, 6)) / 0.1
    _, nbytes = counts.decode_step_need(counts.shape(config), 1.0, live_tokens, 61.0, 2, 2)
    assert value == pytest.approx(100.0 * (nbytes / 819e9) / 30e-3) and ctx.notes["decode_roofline_bound"] == "bytes"


def test_mfu_counts_prompts_and_tokens_that_reached_a_client(run, config):
    s = counts.shape(config)
    records = [_Record(50, [10.01, 10.05, 10.2]), _Record(70, [9.0, 10.02])]        # one first token and two later ones inside
    ctx = _ctx(run, config, [], _trace(), records)
    flops = counts.prefill_flops(s, 50) + counts.decode_flops(s, 51) + counts.decode_flops(s, 71)
    assert run.load_reader(BENCH, "mfu.serve.lfm2moe")(ctx) == pytest.approx(100.0 * flops / (0.1 * 197e12))


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("what", ["no span, no trace", "a commit without these spans", "an empty trace"])
def test_where_there_is_nothing_to_read_a_reader_returns_none(run, config, name, what):
    if what == "no span, no trace":
        ctx = _ctx(run, config, traced=None)
    elif what == "a commit without these spans":            # serve/step without experts_hit, no expert operation in the trace
        ctx = _ctx(run, config, _spans(False), _trace(False))
    else:
        ctx = _ctx(run, config, _spans(False), trace_reduce.Reduced({}, {}, []))
    value = run.load_reader(BENCH, name)(ctx)
    # the whole step's share counts the model's arithmetic from the clients' records: with a trace it reads 0 tokens, not None
    assert value is None or (name == "mfu.serve.lfm2moe" and value == 0.0)
    json.dumps(ctx.notes)


def test_the_expert_roofline_is_none_not_zero_without_the_operation(run, config):
    assert run.load_reader(BENCH, "moe_expert_roofline")(_ctx(run, config, _spans(), _trace(False))) is None


def test_every_new_metric_is_an_entry_a_file_and_reported_in_the_cell(run):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [m["name"] for m in manifest["per_layer"]][-len(NEW):] == NEW
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    reported = {m["name"] for m in run.metrics_for(manifest, cell, "per_layer")}
    assert set(NEW) <= reported and not {"mfu.serve", "decode_step_roofline"} & reported
    assert {m["name"] for m in run.metrics_for(manifest, cell, "end_to_end")} == {"tpot_p95_ms", "tokens_per_s", "setup_s"}
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p95_ms" and callable(run.load_reader(BENCH, m["name"]))


# --------------------------------------------------------------- reference --
def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "lfm2moe.py")) as f:
        text = f.read()
    assert "bigdl_tpu" not in text and "import jax" in text


def test_rehearsal_with_a_trace_fills_the_counter_and_no_device_metric(capsys):
    run = _run_module()
    assert run.main(["--workload", CELL, "--seed", str(2**31 + 78), "--seconds", "2", "--trace", "1", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"window_compiles"} and out["metrics"]["window_compiles"]["value"] == 0
    assert out["notes"]["checked"]["greedy_tokens"] >= 40
