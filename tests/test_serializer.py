"""Native protobuf model format round-trips.

Reference: ``test/.../utils/serializer/SerializerSpec.scala`` — sweeps
registered modules through save+load+re-forward equality. Here a set of
representative architectures (sequential, graph w/ cycles in node links,
recurrent, BN state, shared weights) round-trips through the protowire
format and must produce identical outputs.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from bigdl_tpu import nn
from bigdl_tpu.utils.serializer import save_module, load_module


def roundtrip(model, x, tmp_path, weight_path=None, **fwd):
    model.evaluate()
    y0 = np.asarray(model.forward(jnp.asarray(x)))
    p = str(tmp_path / "model.bigdl")
    wp = str(tmp_path / "model.weights") if weight_path else None
    save_module(model, p, weight_path=wp)
    loaded = load_module(p).evaluate()
    y1 = np.asarray(loaded.forward(jnp.asarray(x)))
    np.testing.assert_allclose(y0, y1, rtol=1e-6, atol=1e-6)
    return loaded


def test_sequential_mlp(tmp_path):
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4),
                      nn.LogSoftMax()).build(3, (5, 8))
    roundtrip(m, np.random.RandomState(0).randn(5, 8).astype("float32"),
              tmp_path)


@pytest.mark.slow
def test_lenet_with_separable_weights(tmp_path):
    from bigdl_tpu.models.lenet import LeNet5
    x = np.random.RandomState(1).randn(2, 1, 28, 28).astype("float32")
    m = LeNet5(10).build(1, x.shape)
    roundtrip(m, x, tmp_path, weight_path=True)
    # the model file alone must NOT contain the tensor table
    import os
    from bigdl_tpu.utils import protowire
    from bigdl_tpu.utils.serializer import MODEL_FILE
    msg = protowire.decode(open(tmp_path / "model.bigdl", "rb").read(),
                           MODEL_FILE)
    assert not msg.get("tensors")
    assert msg["weights_file"] == "model.weights"
    assert os.path.getsize(tmp_path / "model.weights") > 1000


def test_graph_model_cycles(tmp_path):
    # Graph nodes hold prev/next links -> object cycles must round-trip
    inp = nn.Input()
    h = nn.Linear(6, 6)(inp)
    a = nn.ReLU()(h)
    b = nn.Tanh()(h)          # diamond: shared parent
    out = nn.CAddTable()(a, b)
    m = nn.Graph([inp], [out]).build(2, (3, 6))
    roundtrip(m, np.random.RandomState(2).randn(3, 6).astype("float32"),
              tmp_path)


def test_batchnorm_state_roundtrip(tmp_path):
    m = nn.Sequential(nn.Linear(4, 8), nn.BatchNormalization(8)).build(4, (16, 4))
    x = np.random.RandomState(3).randn(16, 4).astype("float32")
    m.training()
    m.forward(jnp.asarray(x))   # populate running stats
    loaded = roundtrip(m, x, tmp_path)
    # running stats (state) preserved, not reset
    s0 = np.concatenate([np.ravel(v) for v in
                         __import__("jax").tree_util.tree_leaves(m.state)])
    s1 = np.concatenate([np.ravel(v) for v in
                         __import__("jax").tree_util.tree_leaves(loaded.state)])
    np.testing.assert_allclose(s0, s1, rtol=1e-6)


def test_recurrent_lstm(tmp_path):
    m = nn.Recurrent(nn.LSTM(5, 7)).build(5, (2, 3, 5))
    roundtrip(m, np.random.RandomState(4).randn(2, 3, 5).astype("float32"),
              tmp_path)


def test_bf16_params(tmp_path):
    m = nn.Linear(4, 4).build(6)
    import jax
    m.params = jax.tree_util.tree_map(
        lambda v: v.astype(jnp.bfloat16), m.params)
    p = str(tmp_path / "m.bigdl")
    save_module(m, p)
    loaded = load_module(p)
    leaves = jax.tree_util.tree_leaves(loaded.params)
    assert all(v.dtype == jnp.bfloat16 for v in leaves)


def test_overwrite_guard(tmp_path):
    m = nn.Linear(2, 2).build(7)
    p = str(tmp_path / "m.bigdl")
    save_module(m, p)
    with pytest.raises(FileExistsError):
        save_module(m, p)
    save_module(m, p, overwrite=True)


def test_no_pickle_in_format(tmp_path):
    m = nn.Linear(2, 2).build(8)
    p = str(tmp_path / "m.bigdl")
    save_module(m, p)
    blob = open(p, "rb").read()
    assert b"pickle" not in blob and blob[:2] != b"PK"  # not a zip either


def test_golden_corpus():
    """Load every COMMITTED fixture (scripts/gen_serializer_corpus.py) and
    assert forward equality with the recorded output — pins the wire format
    across rounds, like the reference's stored models in
    ``test/resources/serializer/`` + ``SerializerSpec.scala``."""
    import os
    root = os.path.join(os.path.dirname(__file__), "data", "serializer")
    names = sorted(f[:-6] for f in os.listdir(root) if f.endswith(".bigdl"))
    assert len(names) >= 20, f"corpus shrank: {names}"
    for name in names:
        model = load_module(os.path.join(root, f"{name}.bigdl")).evaluate()
        x = np.load(os.path.join(root, f"{name}.in.npy"))
        want = np.load(os.path.join(root, f"{name}.out.npy"))
        got = np.asarray(model.forward(jnp.asarray(x)))
        np.testing.assert_allclose(
            got, want, rtol=1e-5, atol=1e-6,
            err_msg=f"golden fixture '{name}' forward drifted")


def test_remote_filesystem_hook():
    """gs://-style paths route through a registered filesystem (reference
    ``utils/File.scala:26``: local/HDFS/S3 via the hadoop fs API)."""
    import io
    from bigdl_tpu.utils.fileio import register_filesystem

    blobs = {}

    class MemFS:
        @staticmethod
        def open(path, mode="rb"):
            if "w" in mode:
                buf = io.BytesIO()
                real_close = buf.close

                def close():
                    blobs[path] = buf.getvalue()
                    real_close()
                buf.close = close
                return buf
            return io.BytesIO(blobs[path])

        @staticmethod
        def exists(path):
            return path in blobs

        @staticmethod
        def makedirs(path):
            pass

    register_filesystem("mem", MemFS)

    model = nn.Sequential().add(nn.Linear(4, 3)).add(nn.Tanh())
    model.build(0, (2, 4))
    x = np.random.RandomState(0).randn(2, 4).astype(np.float32)
    y0 = np.asarray(model.evaluate().forward(jnp.asarray(x)))

    save_module(model, "mem://bucket/model.bigdl",
                weight_path="mem://bucket/model.weights")
    assert "mem://bucket/model.bigdl" in blobs
    loaded = load_module("mem://bucket/model.bigdl").evaluate()
    y1 = np.asarray(loaded.forward(jnp.asarray(x)))
    np.testing.assert_allclose(y0, y1, rtol=1e-6)

    # checkpoint path routing (Optimizer._checkpoint -> join with '/')
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.optim.optimizer import Optimizer
    opt = Optimizer.__new__(Optimizer)
    opt.checkpoint_path = "mem://bucket/ckpt"
    opt.model = model
    opt.optim_method = SGD(learningrate=0.1)
    opt._opt_state = opt.optim_method.init_state(model.params)
    opt._checkpoint(7)
    opt._join_checkpoint()    # the write runs behind, on a worker thread
    assert "mem://bucket/ckpt/model.7" in blobs
    assert "mem://bucket/ckpt/optimMethod.7" in blobs

    # driver-state write + checkpoint listing route through fileio too
    # (retry-from-checkpoint needs both on remote checkpoint paths)
    MemFS.listdir = staticmethod(
        lambda path: [b.rsplit("/", 1)[-1] for b in blobs
                      if b.startswith(path.rstrip("/") + "/")])
    from bigdl_tpu.parallel import DistriOptimizer
    dopt = DistriOptimizer.__new__(DistriOptimizer)
    dopt.checkpoint_path = "mem://bucket/ckpt"
    dopt._save_driver_state({"epoch": 2, "neval": 7, "loss": 0.5,
                             "score": None, "epoch_finished": False})
    assert "mem://bucket/ckpt/driverState.7" in blobs
    assert "mem://bucket/ckpt/driverState.latest" in blobs
    from bigdl_tpu.utils.fileio import file_listdir
    assert "model.7" in file_listdir("mem://bucket/ckpt")
    import pickle
    assert pickle.loads(blobs["mem://bucket/ckpt/driverState.7"])[
        "neval"] == 7
