"""The dots3-note configuration's own arithmetic and readers, on the CPU: the counts against numbers worked out by hand and against the
program's tree, the configuration file against the catalog's published keys it repeats, the four per-layer readers on hand-made spans
and a hand-made trace, the reference's independence of the program, and the cell's rehearsal."""
import json
import os

import pytest

from benchmarks.harness import counts_dots3 as counts
from benchmarks.harness import peaks, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "dots3-longctx-generate"
CONFIG = "dots3-note-prev-ep8-serve"
NEW = ["mfu.serve.dots3", "decode_step_roofline.dots3", "dsa_selected_share", "dsa_select_ms", "latent_attention_roofline",
       "moe_expert_roofline.dots3"]
JOINED = ["tpot_p95_ms", "slot_occupancy_mean", "decode_step_ms", "device_idle_share.serve", "window_compiles", "step_host_ms",
          "moe_experts_hit_mean"]
REDUCED = {"num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"}


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
def test_param_count_is_the_issues_arithmetic(config):
    s = counts.shape(config)
    full = counts.latent_params(s["full"], 5120) + counts.indexer_params(s)
    assert counts.latent_params(s["full"], 5120) == (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
                                                     + 128 * 128 * 5120 + 5120 * 128)                       # 134.7 M
    assert counts.indexer_params(s) == 1024 * 64 * 128 + 5120 * 128 + 5120 * 64                             # 9.4 M
    assert counts.latent_params(s["swa"], 5120) == (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320
                                                    + 64 * 128 * 5120 + 5120 * 64)                          # 90.8 M
    assert counts.expert_params(s) == 3 * 5120 * 1536                                                       # 23.6 M
    # ISSUE 34: layer 0 356 M, layer 1 924 M, a window layer 871 M, embedding + head + final norm 195 M: 4087 M, 8.17 GB
    assert round((full + 3 * 5120 * 13824) / 1e6) == 356
    assert round((full + 5120 * 256 + 33 * counts.expert_params(s)) / 1e6) == 924
    assert round((counts.latent_params(s["swa"], 5120) + 5120 * 256 + 33 * counts.expert_params(s)) / 1e6) == 871
    assert round(counts.param_count(s) / 1e6) == 4087 and round(2 * counts.param_count(s) / 1e9, 2) == 8.17
    assert round(2 * 4 * 32 * counts.expert_params(s) / 1e9, 2) == 6.04                                      # of it routed experts


def test_param_count_is_the_programs_tree(config):
    import jax
    from benchmarks.harness import resolve
    model = resolve(config["constructor"])(**config["constructor_kwargs"])
    tree = jax.eval_shape(lambda k: model.setup(k, None)[0], jax.random.key(0))
    assert sum(int(l.size) for l in jax.tree_util.tree_leaves(tree)) == counts.param_count(counts.shape(config))
    cache = jax.eval_shape(lambda: model.init_cache(config["engine_kwargs"]["max_slots"], "bfloat16"))
    held = sum(int(l.size) * 2 for l in jax.tree_util.tree_leaves(cache))
    key, full, near = counts.row_bytes(counts.shape(config), 2)
    assert (key, full, near) == (256, 1152, 2176)
    # as allocated a row is whole lanes of 128 (640 and 1152 numbers, zeros behind the 576 and 1088: what the device pads a tiled row to
    # anyway): 3.62 + 0.16 GB of tables where the rows alone are 3.32 + 0.15
    assert held == 36 * (2 * 32768 * (key + 640 * 2) + 3 * 640 * 1152 * 2) == 3783131136


def test_the_file_repeats_the_published_keys_and_changes_depth_experts_and_vocabulary(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG)
    changed = {k for k, v in config["published"].items() if config[k] != v}
    assert changed == set(entry["reduced"]) == REDUCED
    assert len(config["reduced"]) == 3 and all(isinstance(r, str) for r in config["reduced"])
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (5, 32, 19008)
    assert config["layer_types"] == config["published"]["layer_types"][:5]
    kw = config["constructor_kwargs"]
    for key, value in config["published"].items():
        if key in kw and key not in REDUCED:
            assert kw[key] == value, key
    # the router keeps its published width and its eight a token; 32 of the 256 are held
    assert (kw["n_routed_experts"], kw["experts_held"], kw["num_experts_per_tok"]) == (256, 32, 8)
    assert (kw["vocab_size"], kw["max_position"], kw["layer_types"]) == (19008, 32768, config["layer_types"])
    assert entry["source"] == config["source"] and entry["file"] == "benchmarks/configs/" + CONFIG + ".json"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f if '"name": "dots3-note-prev"' in line)
        assert row["config"] == config["published"] and row["source_url"] == config["source"]


def test_a_position_scores_its_context_and_reads_the_chosen_rows(config):
    s = counts.shape(config)
    assert counts.rows_read(s, 0) == (1, 1, 1) and counts.rows_read(s, 512) == (513, 513, 513)
    assert counts.rows_read(s, 2047) == (2048, 2048, 513) and counts.rows_read(s, 14000) == (14001, 2048, 513)
    assert counts.index_row_flops(s) == 2 * 64 * 128
    assert counts.step_row_flops(s["full"]) == 2 * 128 * (2 * 512 + 64) and counts.step_row_flops(s["swa"]) == 2 * 64 * (2 * 1024 + 64)
    assert counts.pair_flops(s["full"]) == 2 * 128 * 320 and counts.pair_flops(s["swa"]) == 2 * 64 * 384
    # of a token's 8 experts an eighth is held: one expert's worth a routed layer
    assert counts.token_matrix_flops(s) == 2 * (counts.non_expert_matrix_params(s) + 4 * counts.expert_params(s))
    # past the selection one more position of context is one more row scored in each full layer, and nothing more read
    more = counts.decode_flops(s, 14001) - counts.decode_flops(s, 14000)
    assert more == 2 * counts.index_row_flops(s)
    assert counts.decode_flops(s, 100) - counts.decode_flops(s, 99) == 2 * (counts.index_row_flops(s) + counts.step_row_flops(s["full"])) \
        + 3 * counts.step_row_flops(s["swa"])


@pytest.mark.parametrize("n", [1, 15, 513, 2048, 2049, 12288])
def test_a_prefill_is_the_sum_of_its_positions(config, n):
    s = counts.shape(config)
    assert counts.capped_sum(n, 2048) == sum(min(p + 1, 2048) for p in range(n))
    by_position = sum(2 * (counts.index_row_flops(s) * (p + 1) + counts.pair_flops(s["full"]) * min(p + 1, 2048))
                      + 3 * counts.pair_flops(s["swa"]) * min(p + 1, 513) for p in range(n))
    assert counts.prefill_flops(s, n) == n * counts.token_matrix_flops(s) + by_position + counts.head_flops(s)


def test_a_step_needs_the_rows_read_and_no_other(config):
    s = counts.shape(config)
    flops, nbytes = counts.decode_step_need(s, 36, 500_000, 73_728, 18_468, 21.8, 2, 2)
    weights = counts.non_expert_matrix_params(s) + 5120 * 19008 + 4 * 21.8 * counts.expert_params(s)
    # the index keys scored and the latents read in 2 full layers, the ring rows in 3 window layers, the rows written, 36 rows of logits
    assert nbytes == pytest.approx(weights * 2 + 2 * (256 * 500_036 + 1152 * 73_764) + 3 * 2176 * 18_504 + 36 * 19008 * 4)
    more = counts.decode_step_need(s, 36, 500_001, 73_728, 18_468, 21.8, 2, 2)
    assert (more[0] - flops, round(more[1] - nbytes)) == (2 * 2 * 64 * 128, 2 * 256)
    # reading the context's latents and not the chosen rows would be 2 x 1152 B x 426 000 rows more, 1 GB
    assert 2 * 1152 * (500_000 - 73_728) > 0.9e9
    # bound by bytes: 6.6 GB against 0.15 TFLOP
    p = peaks.peaks_for("TPU v5 lite")
    assert nbytes / p["bytes_per_s"] > 7e-3 and flops / p["flops_per_s"] < 1e-3


# ----------------------------------------------------------------- readers --
STEP = ("jit_step(7)", "jit_prefill(8)")


def _trace():
    """Two launches of the step (12 ms each; 1 ms of each in a sort, 2 ms in a gather) and one prefill that sorts too."""
    ops = []
    for at in (0.000, 0.020):
        ops += [["%fusion.1 = f32[36,5120]{1,0} fusion(...)", at, 0.009], ["%sort.3 = (f32[36,32768], s32[36,32768]) sort(...)", at + 0.009, 0.001],
                ["%gather.48 = bf16[36,2048,576] gather(...)", at + 0.010, 0.002],
                ["%latent_attention.10 = f32[36,128,512] custom-call(...)", at + 0.0105, 0.001],
                ["%ragged-dot.5 = f32[288,1536] ragged-dot(...)", at + 0.0115, 0.00025]]
    ops.append(["%sort.9 = (f32[256,32768], s32[256,32768]) sort(...)", 0.050, 0.020])
    return trace_reduce.Reduced({"0": ops}, {"0": [[STEP[0], 0.0, 0.012], [STEP[0], 0.020, 0.012], [STEP[1], 0.045, 0.500]]}, [])


def _spans(with_rows=True):
    first = {"dsa_context_rows": 500_000, "dsa_selected_rows": 73_728, "swa_rows": 18_468, "assignments_held": 36.0} if with_rows else {}
    second = {"dsa_context_rows": 500_036, "dsa_selected_rows": 73_728, "swa_rows": 18_468, "assignments_held": 35.0} if with_rows else {}
    base = {"live": 36, "kv_write": "scatter", "attn_read": "model", "experts": "ragged_dot", "assignments": 288}
    return [("serve/step", 10.0, 10.014, dict(base, iter=1, experts_hit=22.0, **first)),
            ("serve/step", 10.02, 10.034, dict(base, iter=2, experts_hit=21.0, **second)),
            ("serve/prefill", 10.04, 10.6, dict({"iter": 3, "n": 1, "rows": 1, "bucket": 16384, "tokens": 12288},
                                                 **({"dsa_context_rows": 75_503_616, "dsa_selected_rows": 23_069_696, "swa_rows": 6_172_416}
                                                    if with_rows else {})))]


class _Planned:
    def __init__(self, n):
        self.prompt = [0] * n


class _Record:
    def __init__(self, n0, token_at):
        self.planned, self.token_at = _Planned(n0), token_at


def _ctx(run, config, spans=(), trace=None, records=(), traced=(10.0, 10.1)):
    return run.Ctx(config=config, spans=list(spans), trace=trace, records=list(records), traced=traced, chips=1,
                   peaks=peaks.peaks_for("TPU v5 lite"))


def test_selected_share_reads_the_step_spans(run, config):
    ctx = _ctx(run, config, _spans())
    value = run.load_reader(BENCH, "dsa_selected_share")(ctx)
    assert value == pytest.approx(100.0 * (73_728 / 500_000 + 73_728 / 500_036) / 2) and value < 50.0
    assert ctx.notes["dsa_context_rows_per_stream"] == pytest.approx((500_000 + 500_036) / 2 / 36)


def test_select_ms_sums_the_named_operations_inside_the_step_launches(run, config):
    ctx = _ctx(run, dict(config, select_ops=["sort", "gather"]), _spans(), _trace())
    value = run.load_reader(BENCH, "dsa_select_ms")(ctx)
    assert value == pytest.approx(3.0)                    # 1 ms + 2 ms a launch; the prefill's sort is not a step's
    assert ctx.notes["dsa_select_ms_by_op"] == pytest.approx({"sort": 1.0, "gather": 2.0})
    assert run.load_reader(BENCH, "dsa_select_ms")(_ctx(run, dict(config, select_ops=[]), _spans(), _trace())) is None
    assert run.load_reader(BENCH, "dsa_select_ms")(_ctx(run, dict(config, select_ops=["no-such-op"]), _spans(), _trace())) is None
    assert all(isinstance(n, str) and n for n in config["select_ops"]) and config["expert_op"] == "ragged-dot"


def test_the_configurations_needles_find_the_operations_as_the_trace_names_them(run, config):
    """The trace's own names carry a layout behind every shape; the configuration's needles are written as a run's breakdown prints
    them, without. (The first traced runs of the cell printed no ``dsa_select_ms``: the search ran over the raw names.)"""
    lay = "{1,0:T(8,128)}"
    ops = [[f"%fusion.70 = f32[36,32768]{lay} fusion(bf16[36,32768,128]{{2,1,0:T(8,128)(2,1)}} %get-tuple-element.1191, f32[36,64]{lay} %fusion.348)",
            0.001, 0.0004],
           [f"%while.9 = (s32[]{{:T(128)}}, s32[36]{{0:T(128)}}, s32[36]{{0:T(128)}}, s32[36,32768]{lay}) while(%tuple.5)", 0.002, 0.0003],
           [f"%conditional.5 = (pred[36,32768]{{1,0:T(8,128)(4,1)}}) conditional(s32[]{{:T(128)}} %convert_element_type.199, (pred[36,32768]) %tuple.54)",
            0.003, 0.0002],
           [f"%latent_attention.3 = f32[36,128,512]{{2,1,0:T(8,128)}} custom-call(s32[36]{{0:T(128)}} %copy-done, bf16[36,32768,640]{{2,1,0}} %p)",
            0.004, 0.0014],
           [f"%ragged-dot-none.9 = f32[288,5120]{lay} custom-call(s32[1]{{0:T(128)}} %get-tuple-element.374, s32[33]{{0:T(128)}} %x)", 0.006, 0.0003]]
    ctx = _ctx(run, config, _spans(), trace_reduce.Reduced({"0": ops}, {"0": [[STEP[0], 0.0, 0.012]]}, []))
    assert run.load_reader(BENCH, "dsa_select_ms")(ctx) == pytest.approx(0.4 + 0.3 + 0.2 + 1.4)
    assert sorted(ctx.notes["dsa_select_ms_by_op"]) == sorted(config["select_ops"])
    assert all(v > 0 for v in ctx.notes["dsa_select_ms_by_op"].values())
    assert run.load_reader(BENCH, "latent_attention_roofline")(ctx) is not None and ctx.notes["latent_read_ms_per_step"] == pytest.approx(1.4)
    assert run.load_reader(BENCH, "moe_expert_roofline.dots3")(ctx) is not None and ctx.notes["expert_ms_per_step"] == pytest.approx(0.3)


def test_the_kernels_roofline_counts_the_rows_the_mathematics_reads(run, config):
    ctx = _ctx(run, config, _spans(), _trace())
    value = run.load_reader(BENCH, "latent_attention_roofline")(ctx)
    flops, nbytes = counts.latent_read_need(counts.shape(config), 73_728, 18_468, 2)
    assert nbytes == 2 * 1152 * 73_728 + 3 * 2176 * 18_468 and flops == 2 * 2 * 128 * 1088 * 73_728 + 3 * 2 * 64 * 2112 * 18_468
    assert value == pytest.approx(100.0 * (nbytes / 819e9) / 1e-3) and value < 100.0          # 1 ms a launch in the kernel; bytes bound
    assert ctx.notes["latent_read_ms_per_step"] == pytest.approx(1.0)
    assert config["kernel_op"] == "latent_attention"


def test_the_expert_roofline_counts_the_experts_hit_of_those_held(run, config):
    ctx = _ctx(run, config, _spans(), _trace())
    value = run.load_reader(BENCH, "moe_expert_roofline.dots3")(ctx)
    flops, nbytes = counts.expert_step_need(counts.shape(config), 35.5, 21.5, 2)
    # 4 routed layers x 21.5 experts x 23.6 M parameters x 2 B: 4.06 GB; the 35.5 assignments' arithmetic is a thousandth of the time
    assert nbytes == 4 * 21.5 * 3 * 5120 * 1536 * 2 and flops == 4 * 35.5 * 2 * 3 * 5120 * 1536
    assert value == pytest.approx(100.0 * (nbytes / 819e9) / 0.25e-3)       # 0.25 ms a launch in the hand-made trace
    assert ctx.notes["expert_ms_per_step"] == pytest.approx(0.25)
    # a step span without assignments_held (another model's) is not this reader's
    assert run.load_reader(BENCH, "moe_expert_roofline.dots3")(_ctx(run, config, _spans(False), _trace())) is None


def test_decode_step_roofline_reads_the_rows_of_the_step_spans(run, config):
    ctx = _ctx(run, config, _spans(), _trace())
    value = run.load_reader(BENCH, "decode_step_roofline.dots3")(ctx)
    _, nbytes = counts.decode_step_need(counts.shape(config), 36, 500_018, 73_728, 18_468, 21.5, 2, 2)
    assert value == pytest.approx(100.0 * (nbytes / 819e9) / 12e-3) and value < 100.0
    assert ctx.notes["decode_roofline_bound"] == "bytes" and ctx.notes["decode_least_ms"] == pytest.approx(1e3 * nbytes / 819e9)


def test_mfu_counts_prompts_and_tokens_that_reached_a_client(run, config):
    s = counts.shape(config)
    records = [_Record(3000, [10.01, 10.05, 10.2]), _Record(5000, [9.0, 10.02])]      # one first token and two later ones inside
    ctx = _ctx(run, config, [], _trace(), records)
    # token k + 1 comes out of feeding token k back at position n0 + k - 1
    flops = counts.prefill_flops(s, 3000) + counts.decode_flops(s, 3000) + counts.decode_flops(s, 5000)
    assert run.load_reader(BENCH, "mfu.serve.dots3")(ctx) == pytest.approx(100.0 * flops / (0.1 * 197e12))


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("what", ["no span, no trace", "a commit without these spans", "an empty trace"])
def test_where_there_is_nothing_to_read_a_reader_returns_none(run, config, name, what):
    if what == "no span, no trace":
        ctx = _ctx(run, config, traced=None)
    elif what == "a commit without these spans":            # serve/step without the dsa_* rows: what the parent would leave
        ctx = _ctx(run, config, _spans(False), trace_reduce.Reduced(
            {"0": [["%fusion.1 = f32[36,5120]{1,0} fusion(...)", 0.0, 0.01]]}, {"0": [[STEP[0], 0.0, 0.012]]}, []))
    else:
        ctx = _ctx(run, config, _spans(False), trace_reduce.Reduced({}, {}, []))
    value = run.load_reader(BENCH, name)(ctx)
    # the whole step's share counts the model's arithmetic from the clients' records: with a trace it reads 0, not None
    assert value is None or (name == "mfu.serve.dots3" and value == 0.0)
    json.dumps(ctx.notes)


def test_every_new_metric_is_an_entry_a_file_and_reported_in_the_cell(run):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in names if n in NEW] == NEW                    # in this order, wherever later PRs append theirs
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, CELL, 1) and len(cell["why"]) <= 200
    reported = {m["name"] for m in run.metrics_for(manifest, cell, "per_layer")}
    assert set(NEW) <= reported
    assert not {"mfu.serve", "decode_step_roofline", "mfu.serve.lfm2moe", "moe_expert_roofline", "mfu.serve.evabyte"} & reported
    assert {m["name"] for m in run.metrics_for(manifest, cell, "end_to_end")} == {"tpot_p95_ms", "setup_s"}
    listed = {m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g] if CELL in m.get("workloads", ())}
    assert listed >= set(NEW) | set(JOINED)                         # later metrics may list the cell too
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p95_ms" and callable(run.load_reader(BENCH, m["name"]))


def test_the_traffic_is_the_issues(config):
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], config["engine_kwargs"]["max_slots"]) == ("closed", 40, 36)
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 12288, "sigma": 0.5, "min": 4096, "max": 24576}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 1536, "sigma": 0.5, "min": 512, "max": 4096}
    assert traffic["sampled_share"] == 0.0 and traffic["temperature"] == 0.0 and traffic["lead_in_s"] % 5 == 0
    assert config["engine_kwargs"] == {"max_slots": 36, "max_queue": 64, "prefill_window": 1}


# --------------------------------------------------------------- reference --
def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "dots3.py")) as f:
        text = f.read()
    assert "bigdl_tpu" not in text and "import jax" in text


def test_the_controls_are_the_stated_precision_the_step_below_and_three_planted_faults(config):
    from benchmarks.reference import dots3
    _, controls = dots3.make(dict(config, constructor_kwargs=config["rehearse"]["constructor_kwargs"]))
    assert set(controls) == {"operands:bfloat16", "operands:float8_e4m3fn", "fault:select_all", "fault:select_recent",
                             "fault:window_unbounded"}
    assert config["control"] == "operands:float8_e4m3fn"
    with pytest.raises(ValueError):
        dots3.make(dict(config, faults=["no_such_fault"]))


def test_rehearsal_with_a_trace_fills_the_counter_and_no_device_metric(capsys):
    run = _run_module()
    assert run.main(["--workload", CELL, "--seed", str(2**31 + 34), "--seconds", "3", "--trace", "1", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    counters = {m["name"] for m in run.metrics_for(*run.load_cell(CELL)[:2], "per_layer") if m["source"] == "program_counter"}
    assert set(out["metrics"]) <= counters and out["metrics"]["window_compiles"]["value"] == 0
    assert out["notes"]["checked"]["greedy_tokens"] >= 200


def test_a_planted_fault_makes_the_rehearsal_not_correct(capsys):
    """``--control`` puts both precisions and the three faults through the comparison at the rehearsal's size: each fault, which changes
    what a layer reads, comes out not correct (the rehearsal's sequences pass the selection of 8 and the window of 5)."""
    run = _run_module()
    assert run.main(["--workload", CELL, "--seed", "7", "--seconds", "3", "--trace", "0", "--rehearse", "--control"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    assert set(out["controls"]) == {"operands:bfloat16", "operands:float8_e4m3fn", "fault:select_all", "fault:select_recent",
                                    "fault:window_unbounded"}
    for name in ("operands:float8_e4m3fn", "fault:select_all", "fault:select_recent", "fault:window_unbounded"):
        assert out["controls"][name]["correct_if_control"] is False
