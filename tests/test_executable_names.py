"""The names the benchmark finds the executables by.

Five per-layer metrics of ``BENCHMARK.json`` read the device trace by the
name of an executable, which each configuration under
``benchmarks/configs/`` gives as ``executables``. ``jax.jit`` takes that
name from the Python function it wraps, so a renamed local function would
turn those metrics to ``null`` with every other test green. Here the
program's own functions are lowered on the CPU and their modules' names
compared with what the configurations ask for.
"""

import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu.models.gpt import GPTForCausalLM
from bigdl_tpu.optim import SGD
from bigdl_tpu.parallel import make_distributed_train_step
from bigdl_tpu.serving import SlotManager

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "configs")


def _asked_for():
    """``{(runner, which): name}`` over every configuration's file."""
    out = {}
    for path in sorted(glob.glob(os.path.join(CONFIGS, "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        for which, name in cfg["executables"].items():
            out[cfg["runner"], which] = name
    return out


def _module_name(lowered):
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


@pytest.fixture(scope="module")
def lowered_names():
    """``{(runner, which): name}`` of the modules the program lowers."""
    model = GPTForCausalLM(vocab_size=61, hidden_size=32, n_layers=2,
                           n_heads=4, max_position=64)
    params, _ = model.setup(jax.random.PRNGKey(0), None)
    slots = SlotManager(model, params, max_slots=2, window=2)
    prefill, step = slots._build_fns()
    w = slots.window
    names = {
        ("serve", "prefill"): _module_name(prefill.lower(
            params, slots._cache, slots._logits,
            np.zeros((w, 16), np.int32), np.ones(w, np.int32),
            np.full(w, slots.max_slots, np.int32))),
        ("serve", "step"): _module_name(step.lower(
            params, slots._cache, slots._logits, slots.lengths,
            slots.active, slots.temps, slots._key))}

    net = (nn.Sequential().add(nn.Linear(4, 16)).add(nn.ReLU())
           .add(nn.Linear(16, 3)).add(nn.LogSoftMax())).build(0, (2, 4))
    mesh = Mesh(np.asarray(jax.devices()), axis_names=("data",))
    step_fn, flat, opt_shard = make_distributed_train_step(
        net, nn.ClassNLLCriterion(), SGD(learningrate=0.1), mesh)(net.params)
    rows = NamedSharding(mesh, P("data"))
    n = 2 * mesh.size
    names["train", "step"] = _module_name(step_fn.lower(
        flat, net.state, opt_shard, jax.random.key(0),
        jax.device_put(jnp.zeros((n, 4), jnp.float32), rows),
        jax.device_put(jnp.zeros((n,), jnp.int32), rows)))
    return names


def test_both_configurations_name_their_executables():
    assert _asked_for() == {("serve", "step"): "jit_step",
                            ("serve", "prefill"): "jit_prefill",
                            ("train", "step"): "jit_local_step"}


@pytest.mark.parametrize("runner,which", [
    ("serve", "prefill"), ("serve", "step"), ("train", "step")])
def test_lowered_module_has_the_name_the_benchmark_reads(lowered_names,
                                                         runner, which):
    assert lowered_names[runner, which] == _asked_for()[runner, which]
