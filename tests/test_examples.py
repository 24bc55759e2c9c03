"""Smoke tests: every example main runs end-to-end on tiny configs.

Reference analog: ``pyspark/test/local_integration`` runs the example
scripts; here each main is executed in-process on the CPU backend with
synthetic data (zero egress).
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # runs example mains end-to-end

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(script, *args, timeout=240, subdir="examples"):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["BIGDL_TPU_PLATFORM"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, subdir, script), *args],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_lenet_mnist_example():
    out = run_example("lenet_mnist.py", "-e", "1", "-b", "32")
    assert "Top1Accuracy" in out


def test_resnet_cifar10_example():
    out = run_example("resnet_cifar10.py", "-e", "1", "-b", "32",
                      "--depth", "20", "--synthetic-size", "128")
    assert "Top1Accuracy" in out


def run_script(script, *args, timeout=300):
    return run_example(script, *args, timeout=timeout, subdir="scripts")


def test_lenet_convergence_artifact_contract(tmp_path):
    """The convergence artifact runs the full stack on the real digits
    corpus and emits the JSON record (short budget here; the recorded
    full run is in BASELINE.md round 5)."""
    import json
    out_path = str(tmp_path / "artifact.json")
    out = run_script("train_lenet_convergence.py", "--max-epochs", "2",
                     "--workdir", str(tmp_path / "work"),
                     "--out", out_path)
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["artifact"] == "lenet_convergence"
    assert rec["dataset"] == "sklearn-digits-28x28"
    assert rec["n_train"] == 1437 and rec["n_test"] == 360
    assert 0.0 <= rec["top1"] <= 1.0 and rec["epochs_run"] >= 2
    assert json.load(open(out_path)) == rec
    # the full stack left its artifacts: checkpoint + TB events
    work = tmp_path / "work"
    assert any(f.startswith("model.") for f in os.listdir(work / "ckpt"))
    assert any((work / "lenet").rglob("events.out.tfevents*"))


def test_resnet_smoke_contract(tmp_path):
    import json
    out = run_script("train_resnet_smoke.py", "-e", "1", "-b", "32",
                     "--n", "320", "--floor", "0.0",
                     "--out", str(tmp_path / "r.json"))
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["artifact"] == "resnet_cifar_smoke" and rec["passed"]


def test_ptb_word_lm_example():
    out = run_example("ptb_word_lm.py", "-e", "1", "-b", "8",
                      "--num-steps", "10", "--hidden-size", "32")
    assert "perplexity" in out


def test_autoencoder_example():
    out = run_example("autoencoder_mnist.py", "-e", "1", "-b", "64")
    assert "reconstruction MSE" in out


def test_text_classifier_example():
    out = run_example("text_classifier.py", "-e", "2", "-b", "16",
                      "--seq-len", "40")
    assert "Top1Accuracy" in out


def test_optimizer_perf_harness():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["BIGDL_TPU_PLATFORM"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "optimizer_perf.py"),
         "-m", "lenet", "-b", "16", "-i", "3", "--warmup", "1"],
        env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    import json
    stats = json.loads(r.stdout.strip().splitlines()[-1])
    assert stats["records_per_second"] > 0


def test_inception_v1_aux_heads():
    """VERDICT r1 weak #6: Inception v1 must include the aux classifiers
    (reference Inception_v1.scala:181 concat of [loss3, loss2, loss1])."""
    import numpy as np  # conftest already pins the CPU backend
    import jax.numpy as jnp
    from bigdl_tpu.models.inception import Inception_v1

    m = Inception_v1(class_num=20, has_dropout=False)
    m.build(0, (1, 3, 224, 224)).evaluate()
    y = np.asarray(m.forward(jnp.ones((1, 3, 224, 224), jnp.float32)))
    assert y.shape == (1, 60)
    for s in range(3):  # each head slice is a valid log-softmax
        np.testing.assert_allclose(
            np.exp(y[:, s * 20:(s + 1) * 20]).sum(axis=1), 1.0, rtol=1e-4)


def test_serving_example():
    out = run_example("serving.py", "--requests", "8", "--instances", "2")
    assert "served 8 concurrent requests" in out


def test_inception_example_synthetic():
    out = run_example("inception_imagenet.py", "-e", "1", "-b", "8",
                      "--image-size", "224", timeout=400)
    assert "done" in out


def test_bert_sequence_parallel_example():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["BIGDL_TPU_PLATFORM"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "bert_sequence_parallel.py"),
         "--steps", "3", "--seq-len", "64"],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "done: dp=2 sp=4" in r.stdout


def test_bert_mlm_pretrain_example():
    out = run_example("bert_mlm_pretrain.py", "--steps", "4", "--batch", "4",
                      "--seq-len", "32", "--hidden", "32", "--layers", "1",
                      "--heads", "2", "--vocab", "64")
    assert "masked-LM loss" in out and "tokens/s" in out


def test_treelstm_sentiment_example():
    out = run_example("treelstm_sentiment.py", "-e", "3")
    assert "Top1Accuracy" in out


def test_keras_lenet_example():
    out = run_example("keras_lenet.py", "-e", "1", "-b", "64",
                      "--synthetic-size", "512")
    assert "Top1Accuracy" in out


def test_dlframes_pipeline_example():
    out = run_example("dlframes_pipeline.py", "-e", "10")
    assert "Top1Accuracy" in out


def test_tf_import_export_example():
    out = run_example("tf_import_export.py", "-e", "15")
    assert "round-trip max abs error" in out
    assert "fine-tune loss" in out


def test_load_pretrained_example():
    out = run_example("load_pretrained.py")
    assert out.count("max abs err") == 4
    assert "predicted classes" in out


def test_gpt_char_lm_example():
    out = run_example("gpt_char_lm.py", "--steps", "60", "-b", "8",
                      "--seq-len", "32", "--hidden-size", "64",
                      "--sample", "20")
    assert "sample:" in out and "done" in out
