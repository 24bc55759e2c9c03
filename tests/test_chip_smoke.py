"""The tier-1 twin of ``chip_smoke.py``: the same leg functions at toy
size on the CPU mesh (Pallas kernels interpreted), and the script's own
entry refusing to run without a TPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(vocab_size=64, hidden_size=32, n_layers=2, n_heads=4,
           max_position=64)
WAVES = ((5,), (9, 12), (20, 24, 28))
KERNEL_FLAGS = ("BIGDL_TPU_PAGED_KERNEL",)
# true-f32 CPU matmuls: the reference and the engine differ by summation
# order only
TOL = 1e-4


@pytest.fixture(scope="module")
def toy_params():
    from bigdl_tpu.models.gpt import gpt2_small
    return gpt2_small(**TOY).setup(jax.random.key(0), None)[0]


def test_serve_leg(toy_params):
    rec = chip_smoke.serve_leg(TOY, toy_params, WAVES, 6, TOL, wait_s=120)
    assert rec["ok"] and rec["requests"] == 14
    assert rec["step_traces"] == 1
    assert rec["second_pass_identical"] == "6/6"


def test_serve_leg_kernel_paths(toy_params):
    rec = chip_smoke.serve_leg(
        TOY, toy_params, WAVES, 6, TOL,
        engine_kw={"paged": True, "page_size": 8, "prefill_chunk": 8},
        flags=KERNEL_FLAGS, wait_s=120)
    assert rec["ok"] and rec["second_pass_identical"] == "6/6"
    assert not any(f in os.environ for f in KERNEL_FLAGS)


def test_serve_leg_tp_reports_placement(toy_params, multi_device_cpu):
    rec = chip_smoke.serve_leg(TOY, toy_params, WAVES[:2], 4, TOL,
                               engine_kw={"tp": 2}, wait_s=120)
    assert rec["mesh_devices"] == 2 and rec["tp_degree"] == 2
    assert rec["replicated_despite_spec"] == []


def test_kv_write_leg():
    rec = chip_smoke.kv_write_leg(slots=6, heads=2, seq=256, head_dim=32,
                                  interpret=True)
    assert rec["ok"] and rec["differing_elements"] == 0
    # not on a TPU: the serving step would keep the plain write
    assert rec["selected"] == {"float32": False, "bfloat16": False}


def test_decode_attention_leg():
    rec = chip_smoke.decode_attention_leg(
        tables=(((8, 2, 256, 32), 1, "float32"),
                ((8, 2, 256, 32), 4, "bfloat16")), interpret=True)
    assert rec["ok"] and max(rec["errors"].values()) <= rec["tolerance"]
    # not on a TPU: the serving step would keep the masked read
    assert rec["selected"] == {"float32": False, "bfloat16": False}


def test_sampling_leg():
    rec = chip_smoke.sampling_leg(tables=((12, 1000), (4, 300)), keys=2,
                                  interpret=True)
    assert rec["ok"] and set(rec["differing_tokens"].values()) == {0}
    # not on a TPU: the serving step would keep the sorts
    assert rec["selected"] == {"12x1000": False, "4x300": False}


def test_grouped_product_leg():
    """The sweep's parity at toy widths: a prompt pass's rows (a fifth
    live), a share of the experts held, a step's rows under the rule."""
    rec = chip_smoke.grouped_product_leg(
        shapes=(("toy.prefill", 512, 32, 24, 8, 8, 2, 0.2),
                ("toy.share", 512, 24, 32, 16, 4, 4, 1.0),
                ("toy.step", 96, 32, 24, 8, 8, 2, 1.0)),
        tilings=[(256, 128, 128)], reps=0, interpret=True)
    cases = rec["cases"]
    assert rec["ok"] and [c["rule"] for c in cases.values()] == [
        "gmm", "gmm", "ragged_dot"]
    assert cases["toy.prefill"]["held_rows"] < 512 / 4
    assert set(cases["toy.prefill"]["gmm_ms"]) == {"128x32x24", "256x32x24"}


@pytest.mark.parametrize("dtype,error", [("float32", 2e-6),
                                         ("bfloat16", 2e-2)])
def test_eva_read_leg(dtype, error):
    """The kernel against the masked read and the true read at toy
    tables (two windows of 256, 128 summaries in chunks of 8, 4 heads of
    16): every mix of positions within the rounding of the weights, a
    free slot's NaN rows unseen."""
    rec = chip_smoke.eva_read_leg(tables=(6, 256, 128, 4, 16), chunk=8,
                                  layers=2, dtype=dtype, reps=0,
                                  interpret=True)
    assert rec["ok"] and set(rec["cases"]) == {
        "cell", "edges", "first_window", "deepest", "a_third_free"}
    for case in rec["cases"].values():
        assert case["error"] <= error
        assert max(case["bound_share"].values()) <= 1.0
    assert rec["cases"]["first_window"]["rows_fetched_mean"] <= 256
    # not on a TPU: the decode step would keep the masked read
    assert rec["selected"] is False


def test_latent_read_leg():
    """A full layer and a window layer at toy widths in float32: prefill
    past the selection of 8 and the window of 5, then steps, held to the
    plain reference's rows."""
    rec = chip_smoke.latent_read_leg(
        model_kw=dict(
            vocab_size=50, hidden_size=64, intermediate_size=96,
            num_attention_heads=4, q_lora_rank=16, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            index_n_heads=3, index_head_dim=16, index_topk=8,
            swa_num_attention_heads=2, swa_q_lora_rank=16,
            swa_kv_lora_rank=24, swa_qk_nope_head_dim=24,
            swa_qk_rope_head_dim=8, swa_v_head_dim=16,
            sliding_window_size=5, max_position=64, prefill_block=8),
        first=16, steps=10, dtype="float32",
        tol={"rms": 5e-5, "widest": 5e-5},
        weights_spec={"std": 0.2, "gain_std": 0.1, "bias_std": 0.5})
    assert rec["ok"] and rec["logit_gap"] <= 5e-5 < rec["logit_spread"]
    assert rec["logit_gap_rms"] <= rec["logit_gap"]
    # not on a TPU, and never for a table read by selection
    assert (rec["kv_write"], rec["attn_read"]) == ("scatter", "masked")


def test_train_leg(multi_device_cpu):
    from bigdl_tpu.models.resnet import ResNet
    rec = chip_smoke.train_leg(
        ResNet(class_num=10, depth=8, data_set="cifar10", format="NHWC"),
        (8, 32, 32, 3), 10, 4, jnp.bfloat16)
    assert rec["ok"] and rec["mesh_devices"] == len(multi_device_cpu)
    assert rec["loss_last"] < rec["loss_first"]


def test_verdict_is_last_line_with_two_keys(capsys):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    legs = {"serve": {"ok": True, "setup_s": 1.0},
            "train": {"ok": False, "error": "boom"}}
    assert chip_smoke.report(device, legs, {"dir": None}) == 1
    record, verdict = capsys.readouterr().out.strip().splitlines()
    assert json.loads(verdict) == {"ok": False, "device": device}
    assert record.startswith("chip_smoke: report: ")
    assert json.loads(record.split("report: ", 1)[1])["legs"] == legs
    legs["train"] = {"ok": True}
    assert chip_smoke.report(device, legs, {"dir": None}) == 0
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == {
        "ok": True, "device": device}


def test_entry_refuses_without_tpu():
    # TPU_SKIP_MDS_QUERY: libtpu otherwise waits seconds on a cloud
    # metadata server this host does not have before reporting no device
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_SKIP_MDS_QUERY="1")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "runs on a TPU only" in r.stderr
    assert "JAX_PLATFORMS was 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_ssm_leg():
    """A Mamba layer and an expert layer at toy widths in float32, the
    family's long-memory state: prefill over two blocks, several chunks
    and a ragged last one, then steps, held to the plain reference's
    rows."""
    rec = chip_smoke.ssm_leg(
        model_kw=dict(
            vocab_size=50, hidden_size=32, mamba_num_heads=4,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
            n_routed_experts=16, num_experts_per_tok=4,
            moe_intermediate_size=12, moe_latent_size=16,
            moe_shared_expert_intermediate_size=24, experts_first=4,
            experts_held=4, max_position=64, prefill_block=16),
        lengths=(20, 29), steps=10, dtype="float32",
        tol={"rms": 5e-5, "widest": 5e-5},
        weights_spec={"std": 0.2, "gain_std": 0.1, "bias_std": 0.5})
    assert rec["ok"] and rec["logit_gap"] <= 5e-5 < rec["logit_spread"]
    assert rec["ssm_update"] == "plain" and rec["ssm_slots"] == 2 * 10


def test_ssm_leg_builds_its_own_model_at_the_published_widths():
    """The leg's default model is built as the chip builds it (shapes
    only): its positions are whole prompt blocks, hold the longer prompt
    and its steps, and each prompt crosses a block."""
    import inspect

    import jax

    from bigdl_tpu.models.nemotron_h import NemotronHForCausalLM
    model = NemotronHForCausalLM(**chip_smoke.SSM_LEG_KW)
    shapes = jax.eval_shape(lambda k: model.setup(k, None)[0],
                            jax.random.key(0))
    assert shapes["layers"][0]["mixer"]["A_log"].shape == (128,)
    jax.eval_shape(lambda: model.init_cache(2))
    leg = inspect.signature(chip_smoke.ssm_leg).parameters
    lengths, steps = leg["lengths"].default, leg["steps"].default
    assert model.max_position % model.prefill_block == 0
    assert max(lengths) + steps <= model.max_position
    assert min(lengths) > model.prefill_block
