#!/usr/bin/env python
"""Dummy-data training throughput harness.

Reference: ``models/utils/LocalOptimizerPerf.scala`` (single node) and
``DistriOptimizerPerf.scala:82-128`` (cluster) — constant/random dummy input,
fixed model set, throughput from the optimizer's own metrics.

Usage:
  python scripts/optimizer_perf.py --model inception_v1 --batch-size 128
  python scripts/optimizer_perf.py --model resnet50 --distributed \
      --iterations 20
"""

import argparse
import json
import time


def build_model(name, class_num=1000):
    from bigdl_tpu import models

    if name == "lenet":
        return models.LeNet5(10), (1, 28, 28)
    if name == "alexnet_shape":  # reference uses alexnet via loadmodel
        raise SystemExit("alexnet is not in the zoo; use vgg16/resnet50")
    if name == "inception_v1":
        return models.Inception_v1(class_num), (3, 224, 224)
    if name == "inception_v1_noaux":
        return models.Inception_v1_NoAuxClassifier(class_num), (3, 224, 224)
    if name == "inception_v2":
        return models.Inception_v2(class_num), (3, 224, 224)
    if name == "vgg16":
        return models.Vgg_16(class_num), (3, 224, 224)
    if name == "vgg19":
        return models.Vgg_19(class_num), (3, 224, 224)
    if name == "resnet50":
        return models.ResNet(class_num, depth=50), (3, 224, 224)
    raise SystemExit(f"unknown model {name}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-m", "--model", default="inception_v1",
                    choices=["lenet", "inception_v1", "inception_v1_noaux",
                             "inception_v2", "vgg16", "vgg19", "resnet50"])
    ap.add_argument("-b", "--batch-size", type=int, default=128)
    ap.add_argument("-i", "--iterations", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--data-type", default="constant",
                    choices=["constant", "random"])
    ap.add_argument("--distributed", action="store_true",
                    help="data-parallel over all visible devices")
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from bigdl_tpu import nn
    from bigdl_tpu.utils.engine import Engine

    Engine.init()
    model, shape = build_model(args.model)
    x_shape = (args.batch_size,) + shape
    rng = np.random.default_rng(0)
    x_np = (np.ones(x_shape, np.float32) if args.data_type == "constant"
            else rng.standard_normal(x_shape).astype("float32"))
    y_np = rng.integers(0, 1000, size=(args.batch_size,)).astype("float32")

    if args.distributed:
        from bigdl_tpu.parallel.allreduce import make_distributed_train_step
        from bigdl_tpu.optim import SGD
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = Engine.mesh()
        model.build(0, x_shape)
        factory = make_distributed_train_step(model, nn.ClassNLLCriterion(),
                                              SGD(learningrate=0.01), mesh)
        step_fn, flat, opt_shard = factory(model.params)
        state = jax.device_put(model.state, NamedSharding(mesh, P()))
        sharding = NamedSharding(mesh, P("data"))
        x = jax.device_put(jnp.asarray(x_np), sharding)
        y = jax.device_put(jnp.asarray(y_np), sharding)
        key = jax.random.key(0)

        def run_one(i):
            nonlocal flat, state, opt_shard
            flat, state, opt_shard, loss = step_fn(flat, state, opt_shard,
                                                   jax.random.fold_in(key, i),
                                                   x, y)
            return loss
    else:
        from bigdl_tpu.optim import SGD
        from bigdl_tpu.optim.optimizer import make_train_step
        model.build(0, x_shape)
        method = SGD(learningrate=0.01)
        step_fn = make_train_step(model, nn.ClassNLLCriterion(), method)
        params, state = model.params, model.state
        opt_state = method.init_state(params)
        x, y = jnp.asarray(x_np), jnp.asarray(y_np)
        key = jax.random.key(0)

        def run_one(i):
            nonlocal params, state, opt_state
            params, state, opt_state, loss = step_fn(
                params, state, opt_state, jax.random.fold_in(key, i), x, y)
            return loss

    for i in range(args.warmup):
        loss = run_one(i)
    float(loss)  # host sync: waits for the last step
    t0 = time.perf_counter()
    for i in range(args.iterations):
        loss = run_one(args.warmup + i)
    float(loss)
    dt = time.perf_counter() - t0
    throughput = args.batch_size * args.iterations / dt
    print(json.dumps({
        "model": args.model, "batch_size": args.batch_size,
        "iterations": args.iterations, "distributed": args.distributed,
        "devices": jax.device_count(),
        "records_per_second": round(throughput, 2),
        "seconds_per_iteration": round(dt / args.iterations, 4)}))


if __name__ == "__main__":
    main()
