"""The decode step's state-space update as a Pallas kernel
(``ops/ssm_step.py``), interpreted on the CPU, against its plain ``jnp``
twin: the live slots' states and outputs bit for bit (both are the same
float32 operations in the same order; the suite's XLA flags keep the CPU
from contracting them differently), every free slot's state as it came."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops import ssm_step


def _inputs(slots, heads, p, n, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (slots, heads, p, n)),
            jax.random.uniform(ks[1], (slots, heads)),
            jax.random.normal(ks[2], (slots, heads, p)),
            jax.random.normal(ks[3], (slots, heads, n)),
            jax.random.normal(ks[4], (slots, heads, n)))


@pytest.mark.parametrize("live", [
    [1, 0, 1, 1, 0, 0, 1], [1] * 7, [0] * 7, [0, 0, 0, 0, 0, 0, 1],
    [1, 0, 0, 0, 0, 0, 0]], ids=["some", "all", "none", "last", "first"])
@pytest.mark.parametrize("heads", [4, 64], ids=["one_block", "two_blocks"])
def test_live_slots_bit_for_bit_and_free_slots_untouched(live, heads):
    live = np.asarray(live, bool)
    state, decay, dtx, b, c = _inputs(7, heads, 8, 128)
    want_s, want_y = jax.jit(ssm_step.plain_update)(state, decay, dtx, b, c,
                                                     jnp.asarray(live))
    got_s, got_y = ssm_step.ssm_update(state, decay, dtx, b, c, live,
                                       interpret=True)
    got_s, got_y, want_s, want_y = map(np.asarray,
                                       (got_s, got_y, want_s, want_y))
    assert (got_s[live] == want_s[live]).all()
    assert (got_y[live] == want_y[live]).all()
    assert (got_s[~live] == np.asarray(state)[~live]).all()
    if live.any():
        # the update did something: the decay and the rank-1 term
        assert (got_s[live] != np.asarray(state)[live]).any()


def test_the_twin_is_the_recurrence():
    state, decay, dtx, b, c = _inputs(3, 2, 4, 8, seed=1)
    live = jnp.asarray([True, False, True])
    new, y = ssm_step.plain_update(state, decay, dtx, b, c, live)
    s = np.asarray(state)
    want = np.asarray(decay)[..., None, None] * s \
        + np.asarray(dtx)[..., None] * np.asarray(b)[:, :, None, :]
    want[1] = s[1]
    np.testing.assert_allclose(np.asarray(new), want, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(y), np.einsum("shpn,shn->shp", want, np.asarray(c)),
        rtol=1e-5, atol=1e-5)


def test_under_jit_with_a_donated_state():
    """As the serving step calls it: inside a jitted function whose state
    argument is donated, twice in a row, the second on the first's
    output."""
    state, decay, dtx, b, c = _inputs(5, 4, 8, 128, seed=2)
    live = jnp.asarray([True, True, False, True, False])

    @jax.jit
    def twice(state, live):
        for _ in range(2):
            state, y = ssm_step.ssm_update(state, decay, dtx, b, c, live,
                                           interpret=True)
        return state, y

    want = state
    for _ in range(2):
        want, want_y = ssm_step.plain_update(want, decay, dtx, b, c, live)
    keep = np.asarray(state)
    got, y = jax.jit(twice, donate_argnums=0)(state, live)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[[0, 1, 3]],
                               np.asarray(want_y)[[0, 1, 3]], rtol=1e-5,
                               atol=1e-5)
    assert (np.asarray(got)[[2, 4]] == keep[[2, 4]]).all()


@pytest.mark.parametrize("shape, dtype, applies", [
    ((128, 128, 64, 128), jnp.float32, True),
    ((128, 128, 64, 128), jnp.bfloat16, False),
    ((128, 128, 64, 100), jnp.float32, False),
    ((128, 128, 60, 128), jnp.float32, False),
])
def test_what_selects_the_kernel(shape, dtype, applies, monkeypatch):
    """On a TPU a float32 state of whole tiles; anything else, and every
    CPU run, keeps the twin."""
    state = jax.ShapeDtypeStruct(shape, dtype)
    assert not ssm_step.applies(state)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm_step.applies(state) == applies
    assert not ssm_step.applies(state, layout=object())
