"""Mixture-of-Experts FFN with top-k gating + expert parallelism.

No reference analog (the reference predates MoE; SURVEY.md section 2.6
lists data parallelism only) — TPU-native green-field in the GShard/Switch
mold: static-shape capacity dispatch expressed as einsums (the MXU-friendly
formulation), and expert parallelism as a ``shard_map`` over an ``expert``
mesh axis where capacity buffers travel by ``lax.all_to_all``.

Dispatch (per top-k choice c): tokens pick expert e = argmax of the
(masked) gate probs; a position-in-expert cursor (cumsum over tokens)
drops tokens beyond ``capacity``; one-hot dispatch (N, E, C) routes token
vectors into per-expert buffers, experts run a GELU MLP batched over E,
and the combine einsum scatters outputs back weighted by the gate.

:class:`RoutedExperts`, further down, is the layer that serves: it drops
no token (``MoE`` does, beyond ``capacity``), scores with a sigmoid and
a selection bias, and computes its experts as one grouped product over
the assignments sorted by expert, the product chosen by their number
(:func:`grouped_product`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.gated import mm
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops.grouped_matmul import grouped_matmul

# the assignments a routed layer's call takes from which its grouped
# product is the Pallas grouped matmul and not ``ragged_dot``: every
# prompt pass's, 512 and more, and not a decode step's, 288 (dots3) and
# 384 (LFM2). Measured (my chip sweeps, PERF.md section 6, PR 38), the
# grouped matmul takes 0.53-0.79 ms a call at LFM2's 512-8192 prompt rows
# where ``ragged_dot`` takes 1.16-1.51, and it also leads at the steps'
# rows (0.63-0.64 against 0.82-0.84 ms at 384, 0.48-0.61 against
# 0.51-0.66 at 288): there is no crossover above 288. The steps stay on
# ``ragged_dot`` because both ``moe_expert_roofline`` metrics read its
# operations in a step (PERF.md section 7.2)
GROUPED_MATMUL_ROWS = 512


def grouped_product(rows):
    """The grouped product a routed layer runs ``rows`` assignments as:
    ``"gmm"`` (``ops/grouped_matmul.py``) from :data:`GROUPED_MATMUL_ROWS`
    on, else ``"ragged_dot"``. The layer and the slot table's spans both
    ask this, so they cannot disagree."""
    return "gmm" if rows >= GROUPED_MATMUL_ROWS else "ragged_dot"


def _topk_dispatch(probs, k, capacity):
    """probs (N, E) -> (dispatch (N, E, C) one-hot, combine (N, E, C))."""
    n, e = probs.shape
    remaining = probs
    dispatch = jnp.zeros((n, e, capacity), probs.dtype)
    combine = jnp.zeros((n, e, capacity), probs.dtype)
    # per-expert write cursor shared across the k choices
    base_pos = jnp.zeros((e,), jnp.int32)
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                 # (N,)
        gate = jnp.take_along_axis(remaining, idx[:, None], 1)[:, 0]
        onehot = jax.nn.one_hot(idx, e, dtype=probs.dtype)    # (N, E)
        # position of each token within its chosen expert's buffer
        pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)      # (N, E)
        pos = jnp.sum(pos_in_e * onehot, axis=-1).astype(jnp.int32) \
            + base_pos[idx]
        keep = pos < capacity
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity),
                                capacity, dtype=probs.dtype)  # (N, C)
        d = onehot[:, :, None] * pos_oh[:, None, :] \
            * keep[:, None, None].astype(probs.dtype)
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        base_pos = base_pos + jnp.sum(onehot, axis=0).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)
    return dispatch, combine


class MoE(Module):
    """Top-k mixture-of-experts GELU MLP.

    Input (B, T, d) or (N, d); output the same shape. ``capacity_factor``
    sizes the per-expert buffer: C = ceil(k * N * factor / E) (per source
    shard in the expert-parallel case). ``expert_parallel``: None or
    ("shard_map-outer", axis, ndev)-style tuple ``(axis, ndev)`` meaning
    apply() runs INSIDE a shard_map carrying ``axis`` with experts split
    ndev ways; tokens are the local shard's.

    The Switch-style load-balance auxiliary loss is returned in the state
    dict (``{"aux_loss": ...}``) — add it to the training objective
    scaled by ~1e-2 to keep experts balanced.
    """

    def __init__(self, hidden_size, ffn_size, n_experts, k=2,
                 capacity_factor=1.25, expert_parallel=None):
        super().__init__()
        if k < 1 or k > n_experts:
            raise ValueError(f"k={k} outside [1, {n_experts}]")
        self.hidden_size = hidden_size
        self.ffn_size = ffn_size
        self.n_experts = n_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.expert_parallel = expert_parallel

    def make_params(self, rng, input_spec):
        """Always GLOBAL expert shapes; under expert parallelism shard the
        leading E dim of w1/w2 over the expert axis (``param_specs``) and
        the shard_map slices arrive local."""
        d, h, e = self.hidden_size, self.ffn_size, self.n_experts
        k1, k2, k3 = jax.random.split(rng, 3)
        s1 = (2.0 / d) ** 0.5
        return {"wg": jax.random.normal(k1, (d, self.n_experts)) * 0.02,
                "w1": jax.random.normal(k2, (e, d, h)) * s1,
                "w2": jax.random.normal(k3, (e, h, d)) * (2.0 / h) ** 0.5}

    def param_specs(self):
        """PartitionSpec tree for shard_map in_specs under expert
        parallelism: gate replicated, experts sharded on the E dim."""
        from jax.sharding import PartitionSpec as P
        if self.expert_parallel is None:
            return {"wg": P(), "w1": P(), "w2": P()}
        axis = self.expert_parallel[0]
        return {"wg": P(), "w1": P(axis), "w2": P(axis)}

    def _capacity(self, n_tokens):
        import math
        return max(int(math.ceil(self.k * n_tokens * self.capacity_factor
                                 / self.n_experts)), 1)

    def _experts(self, params, buf):
        """buf (E_local, C, d) -> (E_local, C, d): batched GELU MLP."""
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", buf,
                                   params["w1"].astype(buf.dtype)))
        return jnp.einsum("ech,ehd->ecd", h, params["w2"].astype(buf.dtype))

    def apply(self, params, state, x, *, training=False, rng=None):
        shape = x.shape
        tokens = x.reshape(-1, shape[-1])
        n = tokens.shape[0]
        probs = jax.nn.softmax(
            (tokens @ params["wg"].astype(tokens.dtype))
            .astype(jnp.float32), axis=-1)
        cap = self._capacity(n)
        dispatch, combine = _topk_dispatch(probs, self.k, cap)
        dispatch = dispatch.astype(tokens.dtype)
        combine = combine.astype(tokens.dtype)

        if self.expert_parallel is None:
            buf = jnp.einsum("nec,nd->ecd", dispatch, tokens)
            out = self._experts(params, buf)
            y = jnp.einsum("nec,ecd->nd", combine, out)
        else:
            axis, ndev = self.expert_parallel
            e_loc = self.n_experts // ndev
            # (N, E, C) buffers -> per-device expert shards via all_to_all:
            # split the expert dim, concat a source-shard dim onto C
            buf = jnp.einsum("nec,nd->ecd", dispatch, tokens)   # (E, C, d)
            buf = buf.reshape(ndev, e_loc, cap, buf.shape[-1])
            # a2a: dim0 (dest expert shard) scatters; gathered source
            # shards stack along a new leading dim -> (ndev_src, e_loc, C, d)
            buf = lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                 tiled=True).reshape(
                ndev, e_loc, cap, buf.shape[-1])
            # merge source shards into the expert's token buffer
            buf = buf.transpose(1, 0, 2, 3).reshape(
                e_loc, ndev * cap, buf.shape[-1])
            out = self._experts(params, buf)                    # (e_loc, ...)
            out = out.reshape(e_loc, ndev, cap, out.shape[-1]) \
                .transpose(1, 0, 2, 3).reshape(ndev * e_loc, cap,
                                               out.shape[-1])
            out = lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                                 tiled=True)                    # back home
            y = jnp.einsum("nec,ecd->nd", combine, out)

        # Switch load-balance aux: E * sum_e f_e * P_e
        f = jnp.mean(dispatch.sum(-1), axis=0)       # fraction routed
        p = jnp.mean(probs, axis=0).astype(f.dtype)
        aux = self.n_experts * jnp.sum(f * p) / self.k
        return y.reshape(shape), {"aux_loss": aux}


class SquaredReLUMLP(Module):
    """``w2(relu(w1 x)^2)``: the feed-forward of ``mlp_hidden_act: relu2``
    (no gate)."""

    def __init__(self, hidden_size, ffn_size):
        super().__init__()
        self.hidden_size = hidden_size
        self.ffn_size = ffn_size

    def make_params(self, rng, input_spec):
        d, f = self.hidden_size, self.ffn_size
        k1, k2 = jax.random.split(rng)
        return {"w1": jax.random.normal(k1, (d, f)) * d ** -0.5,
                "w2": jax.random.normal(k2, (f, d)) * f ** -0.5}

    def call(self, params, x):
        return mm(jnp.square(jax.nn.relu(mm(x, params["w1"]))), params["w2"])


class RoutedExperts(Module):
    """Dropless routed experts, as the sparse decoders after 2024 route
    (sigmoid scores, a bias that moves the CHOICE only, weights
    renormalised over the chosen):

        s = sigmoid(x @ wg)                       ``num_experts`` scores
        chosen = the ``k`` largest of s + expert_bias
        w_e = s_e / (sum of the chosen s + 1e-6) * scaling
        y = sum over chosen e of w_e * E_e(x)

    ``act`` is the experts' form: ``"swiglu"``, ``E_e(x) = w2[e](silu(w1[e]
    x) * w3[e] x)``, or ``"relu2"``, ``E_e(x) = w2[e](relu(w1[e] x)^2)``.
    With ``latent_size`` the experts work at that width between two
    matrices that every expert shares (LatentMoE): the router still
    scores the full-width ``x``, ``l = x @ w_down``, and ``y = (sum over
    chosen e of w_e * E_e(l)) @ w_up``.

    The layer is told which experts it HOLDS: ``count`` of them from
    ``first`` on (default: all). It routes over all ``num_experts``,
    keeps the assignments to its own, sorts them by expert and computes
    them as one grouped product a matrix. Which product is a function of
    the rows alone, seen when the layer is traced
    (:func:`grouped_product`): under :data:`GROUPED_MATMUL_ROWS`
    assignments, a decode step's, ``jax.lax.ragged_dot`` (the TPU
    compiler's own grouped matmul, ``ragged-dot`` in a device trace);
    from there on, a prompt pass's, the Pallas grouped matmul of
    ``ops/grouped_matmul.py``. What the absent experts would have added
    is left out: the shares of every holder add up to the whole layer.
    No assignment is dropped, whatever the routing: the product's groups
    are as long as the routing makes them.

    Scores, choice and weights are float32 with true-float32 products;
    the expert products take the weights' dtype and sum in float32.
    """

    def __init__(self, hidden_size, ffn_size, num_experts, k, first=0,
                 count=None, use_bias=True, norm_topk_prob=True,
                 scaling=1.0, act="swiglu", latent_size=None):
        super().__init__()
        count = num_experts - first if count is None else count
        if act not in ("swiglu", "relu2"):
            raise ValueError(f"unknown expert form {act!r}")
        if k < 1 or k > num_experts:
            raise ValueError(f"k={k} outside [1, {num_experts}]")
        if first < 0 or count < 1 or first + count > num_experts:
            raise ValueError(f"experts [{first}, {first + count}) outside "
                             f"[0, {num_experts})")
        self.hidden_size = hidden_size
        self.ffn_size = ffn_size
        self.num_experts = num_experts
        self.k = k
        self.first = first
        self.count = count
        self.use_bias = use_bias
        self.norm_topk_prob = norm_topk_prob
        self.scaling = scaling
        self.act = act
        self.latent_size = latent_size

    def make_params(self, rng, input_spec):
        d, f, e, c = (self.hidden_size, self.ffn_size, self.num_experts,
                      self.count)
        kg, k1, k2, k3 = jax.random.split(rng, 4)
        w = self.latent_size or d
        params = {"wg": jax.random.normal(kg, (d, e)) * d ** -0.5,
                  "w1": jax.random.normal(k1, (c, w, f)) * w ** -0.5,
                  "w2": jax.random.normal(k3, (c, f, w)) * f ** -0.5}
        if self.act == "swiglu":
            params["w3"] = jax.random.normal(k2, (c, w, f)) * w ** -0.5
        if self.latent_size:
            k4, k5 = jax.random.split(jax.random.fold_in(rng, 4))
            params["w_down"] = jax.random.normal(k4, (d, w)) * d ** -0.5
            params["w_up"] = jax.random.normal(k5, (w, d)) * w ** -0.5
        if self.use_bias:
            params["expert_bias"] = jnp.zeros((e,))
        return params

    def route(self, params, x):
        """``x`` (N, hidden) -> ``(chosen (N, k) int32, weights (N, k)
        float32)`` over all ``num_experts``, in float32."""
        logits = jnp.dot(x.astype(jnp.float32),
                         params["wg"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        biased = scores
        if self.use_bias:
            biased = scores + params["expert_bias"].astype(jnp.float32)
        _, chosen = lax.top_k(biased, self.k)
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        if self.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
        return chosen.astype(jnp.int32), w * self.scaling

    def routed(self, params, x, live=None):
        """``x`` (N, hidden) -> ``(y (N, hidden) float32, hit)``. ``live``
        (N,) bool marks the rows that count (default: all): a dead row
        (a free slot, a prompt's padding) is left out of the product and
        gets zeros. ``hit`` is how many of the experts held got at least
        one assignment from a live row: the groups that are not empty,
        whose weights the product has to read."""
        y, sizes = self.routed_sizes(params, x, live)
        return y, jnp.sum(sizes > 0, dtype=jnp.int32)

    def routed_sizes(self, params, x, live=None):
        """As :meth:`routed` with the groups' sizes (count,) int32 in
        place of their number: how many of the live rows' assignments
        fell on each expert held."""
        n, k, c = x.shape[0], self.k, self.count
        chosen, w = self.route(params, x)
        local = chosen.reshape(-1) - self.first               # (N * k,)
        mine = (local >= 0) & (local < c)
        if live is not None:
            mine = mine & jnp.repeat(jnp.asarray(live, bool), k)
        # what is not computed here sorts behind every group and falls
        # outside the product's groups
        group = jnp.where(mine, local, c)
        order = jnp.argsort(group, stable=True)
        back = jnp.argsort(order)
        sizes = jnp.sum(
            group[:, None] == jnp.arange(c, dtype=group.dtype)[None, :],
            axis=0, dtype=jnp.int32)
        dt = params["w1"].dtype
        if self.latent_size:
            x = mm(x, params["w_down"])                       # (N, latent)
        xs = jnp.take(x.astype(dt), order // k, axis=0)       # (N * k, d)

        def grouped(a, b):
            if grouped_product(n * k) == "gmm":
                return grouped_matmul(a.astype(dt), b, sizes)
            return lax.ragged_dot(a.astype(dt), b, sizes,
                                  preferred_element_type=jnp.float32)

        if self.act == "relu2":
            h = jnp.square(jax.nn.relu(grouped(xs, params["w1"])))
        else:
            h = jax.nn.silu(grouped(xs, params["w1"])) \
                * grouped(xs, params["w3"])
        ys = jnp.take(grouped(h, params["w2"]), back, axis=0)
        ys = jnp.where(mine[:, None], ys, 0.0).reshape(n, k, -1)
        y = jnp.sum(ys * w[:, :, None], axis=1)
        if self.latent_size:
            y = mm(y, params["w_up"])
        return y, sizes

    def call(self, params, x):
        shape = x.shape
        y, _ = self.routed(params, x.reshape(-1, shape[-1]))
        return y.reshape(shape).astype(x.dtype)


class SharedAndRoutedExperts(Module):
    """:class:`RoutedExperts` beside ``n_shared`` experts that every
    token passes through (the DeepSeek line's shared experts): ``y =
    routed(x) + shared(x)``, the shared ones one feed-forward of the
    routed experts' form (``act``) at full width, ``shared_size`` wide
    (default ``n_shared x ffn_size``). Under expert parallelism every
    holder computes the shared part alike, so it counts ONCE when the
    holders' shares are added up. Arguments after ``shared_size`` are
    :class:`RoutedExperts`'s."""

    def __init__(self, hidden_size, ffn_size, num_experts, k, n_shared=1,
                 shared_size=None, **routed_kw):
        super().__init__()
        from bigdl_tpu.nn.gated import GatedMLP
        self.experts = RoutedExperts(hidden_size, ffn_size, num_experts, k,
                                     **routed_kw)
        width = shared_size or n_shared * ffn_size
        self.shared = SquaredReLUMLP(hidden_size, width) \
            if self.experts.act == "relu2" else GatedMLP(hidden_size, width)

    def make_params(self, rng, input_spec):
        k1, k2 = jax.random.split(rng)
        return {"routed": self.experts.make_params(k1, None),
                "shared": self.shared.make_params(k2, None)}

    def routed(self, params, x, live=None):
        """As ``RoutedExperts.routed`` with the shared experts added,
        and, third, how many of the live rows' assignments fell on the
        experts held."""
        y, sizes = self.experts.routed_sizes(params["routed"], x, live)
        return (y + self.shared.call(params["shared"], x),
                jnp.sum(sizes > 0, dtype=jnp.int32), jnp.sum(sizes))

    def call(self, params, x):
        shape = x.shape
        y, _, _ = self.routed(params, x.reshape(-1, shape[-1]))
        return y.reshape(shape).astype(x.dtype)
