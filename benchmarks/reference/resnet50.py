"""Bottleneck ResNet (He et al. 2015, table 1) and its SGD training step,
as plain float32 ``jax.numpy``/``lax``: forward, loss, gradients, update.

``conv1`` 7x7/2 (pad 3), batch norm, ReLU, 3x3/2 max pool (pad 1), then
the stages of bottleneck blocks (1x1, 3x3 carrying the stride, 1x1; a
projection shortcut with its own batch norm where the shape changes, type
B), global average pool, the classifier, log-softmax and the mean
negative log-likelihood. Batch norm uses the batch's own statistics
(biased variance, eps 1e-5). SGD as the paper's section 3.4 with the
configuration's numbers: ``g += weightdecay * w`` on every leaf,
``v = momentum * v + (1 - dampening) * g``, ``w -= learningrate * v``.

Layout NHWC, filters HWIO, the classifier ``(in, out)``. The parameter
tree is the one ``benchmarks/harness/weights.py`` fills: a dict keyed by
whole numbers as strings, one entry a layer, numbered in the order the
layers are listed above (within a block: the projection and its norm
first, then the three convolutions each followed by its norm). The
reference walks them in that order and checks every shape; it imports
nothing of the program.

The reference multiplies in true float32
(``jax.default_matmul_precision("highest")``) and rematerialises each
block in the backward pass, so that 256 rows fit one chip. A control is
the same code one precision down, put in the program's place (``make``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

STAGES = {50: (3, 4, 6, 3)}
EPS = 1e-5


# ------------------------------------------------------------- rounding --
def _scaled_round(x, dt):
    top = float(jnp.finfo(dt).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dt).astype(x.dtype) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _q(x, kind):
    """``x`` as an 8-bit (or bfloat16) product would see it: rounded to the
    type and back, one scale a tensor for an 8-bit float. In the backward
    pass the gradient that flows through is rounded likewise (an 8-bit
    float to ``float8_e5m2``, the wider range gradients need)."""
    if kind == "bfloat16":
        return x.astype(jnp.bfloat16).astype(x.dtype)
    return _scaled_round(x, jnp.float8_e4m3fn)


def _q_fwd(x, kind):
    return _q(x, kind), None


def _q_bwd(kind, _, ct):
    if kind == "bfloat16":
        return (ct.astype(jnp.bfloat16).astype(ct.dtype),)
    return (_scaled_round(ct, jnp.float8_e5m2),)


_q.defvjp(_q_fwd, _q_bwd)


def _operand(x, kind):
    return x if kind is None else _q(x, kind)


# -------------------------------------------------------------- forward --
def _conv(x, w, stride, pad, kind):
    return lax.conv_general_dilated(
        _operand(x, kind), _operand(w, kind), (stride, stride),
        [(pad, pad), (pad, pad)], dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(p, x):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + EPS) * p["weight"] + p["bias"]


def _conv_bn(layers, x, stride, pad, kind, relu=True):
    x = _conv(x, next(layers)["weight"], stride, pad, kind)
    x = _batch_norm(next(layers), x)
    return jax.nn.relu(x) if relu else x


def _block(entries, x, stride, project, kind):
    layers = iter(entries)
    shortcut = x
    if project:
        shortcut = _conv_bn(layers, x, stride, 0, kind, relu=False)
    y = _conv_bn(layers, x, 1, 0, kind)
    y = _conv_bn(layers, y, stride, 1, kind)
    y = _conv_bn(layers, y, 1, 0, kind, relu=False)
    return jax.nn.relu(y + shortcut)


def layer_list(params):
    """The tree's entries that hold parameters, in the order of their
    whole-number keys (a layer without parameters has an empty entry)."""
    return [params[k] for k in sorted(params, key=int) if params[k]]


def log_probs(params, x, depth=50, kind=None):
    """Log-probabilities ``(rows, classes)`` of the images ``x`` (rows,
    h, w, 3), batch statistics in every norm."""
    entries = layer_list(params)
    at = 0

    def take(n):
        nonlocal at
        at += n
        return entries[at - n:at]

    x = _conv_bn(iter(take(2)), x, 2, 3, kind)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    width = 64
    for si, blocks in enumerate(STAGES[depth]):
        planes = 64 * 2 ** si
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            project = width != planes * 4 or stride != 1
            block = jax.checkpoint(functools.partial(
                _block, stride=stride, project=project, kind=kind))
            x = block(take(8 if project else 6), x)
            width = planes * 4
    x = jnp.mean(x, axis=(1, 2))
    fc, = take(1)
    if at != len(entries):
        raise ValueError(f"the tree has {len(entries)} layers, the "
                         f"architecture {at}")
    logits = _operand(x, kind) @ _operand(fc["weight"], kind) + fc["bias"]
    return jax.nn.log_softmax(logits, axis=-1)


def loss_fn(params, x, y, depth=50, kind=None, rows=None):
    """Mean negative log-likelihood of the labels ``y`` (0-based); with
    ``rows`` only the first ``rows`` of the batch are seen at all."""
    if rows is not None:
        x, y = x[:rows], y[:rows]
    logp = log_probs(params, x, depth, kind)
    picked = jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None], axis=1)
    return -jnp.mean(picked)


# ----------------------------------------------------------------- steps --
def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def make_step(config, kind=None, fault=None):
    """``step(params, velocity, x, y) -> (params, velocity, loss, grads)``,
    jitted once. ``fault`` plants what the comparison has to catch:
    ``"half_batch"`` leaves the second half of the rows out and takes the
    mean over the rest; ``"state_unchanged"`` returns its state as it
    came."""
    o = config["optim_kwargs"]
    lr, mom = float(o["learningrate"]), float(o.get("momentum", 0.0))
    damp = float(o.get("dampening", mom))
    wd = float(o.get("weightdecay", 0.0))
    depth = int(config["constructor_kwargs"]["depth"])

    @jax.jit
    def step(params, velocity, x, y):
        rows = x.shape[0] // 2 if fault == "half_batch" else None
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, x, y, depth, kind, rows)
        if fault == "state_unchanged":
            return params, velocity, loss, grads
        g = _tmap(lambda gg, p: gg + wd * p, grads, params)
        velocity = _tmap(lambda v, gg: mom * v + (1 - damp) * gg, velocity, g)
        params = _tmap(lambda p, v: p - lr * v, params, velocity)
        return params, velocity, loss, grads

    return step


def make(config):
    """``reference(params, batches)`` and ``others``: a dict of ``name ->
    f(params, batches)`` of the same form, each following the first steps
    from ``params`` over ``batches`` (a list of ``(x, y)``) and returning
    ``(losses, first_gradient, change)``: each step's loss, the first
    step's gradient as the optimizer gets it, and the parameters' change
    after the last step, both as trees like ``params``.

    ``config["controls"]`` names the lower-precision controls,
    ``"operands:<kind>"``: true float32 sums, but both operands of every
    convolution and of the classifier's product rounded to ``<kind>``
    (``float8``: e4m3 forward, e5m2 for the gradients flowing back, one
    scale a tensor; ``bfloat16``). ``config["faults"]`` names the faults
    planted in the reference, ``"fault:<name>"``."""

    def follow(step):
        def run(params, batches):
            start = params
            velocity = _tmap(jnp.zeros_like, params)
            losses, first = [], None
            for x, y in batches:
                params, velocity, loss, grads = step(params, velocity, x, y)
                losses.append(float(loss))
                if first is None:
                    first = grads
            return losses, first, _tmap(jnp.subtract, params, start)
        return run

    others = {}
    for name in config.get("controls", ()):
        others[name] = follow(make_step(config, kind=name.split(":", 1)[1]))
    for name in config.get("faults", ()):
        others["fault:" + name] = follow(make_step(config, fault=name))
    return follow(make_step(config)), others
