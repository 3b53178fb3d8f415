"""The float32 references compute what the program computes: at a small
size, the program's own float32 forward pass and the reference agree to
rounding, on weights both make from the same seed."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.tests import tiny

SEED = 2 ** 32 + 3


@pytest.mark.parametrize("name", ["yi-6b", "rwkv6-3b"])
def test_reference_matches_program_forward(name):
    from repro.models.model import Model
    c = tiny.config(name, dtype="float32")
    fam = harness.family(c)
    arch = harness.program_arch(c)
    model = Model(arch)
    params = weights.program_params(model.param_specs(), fam.rules(c), SEED,
                                    arch.num_layers, jnp.float32)
    toks = np.random.default_rng(0).integers(0, 512, (1, 70), np.int32)
    got = np.asarray(model.forward(params, {"tokens": jnp.asarray(toks)})[0][0])
    want = fam.logits_at(c, SEED, toks, [(0, i) for i in range(70)],
                         dtype=jnp.float32)
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


@pytest.mark.parametrize("name", ["yi-6b", "rwkv6-3b"])
def test_reference_layers_are_the_program_weights(name):
    """Every layer the reference makes on its own equals, bit for bit, the
    slice of the program's stacked parameters."""
    from repro.models.model import Model
    c = tiny.config(name)
    fam = harness.family(c)
    arch = harness.program_arch(c)
    params = weights.program_params(Model(arch).param_specs(), fam.rules(c),
                                    SEED, arch.num_layers, jnp.bfloat16)
    make = weights.layer_maker(fam.layer_shapes(c), fam.rules(c), SEED,
                               arch.num_layers, jnp.bfloat16)
    for i in range(arch.num_layers):
        for k, v in make(i).items():
            p = params["stack"]["slots"][0][k][i].astype(jnp.float32)
            assert np.array_equal(np.asarray(p), np.asarray(v)), (i, k)
    glob = weights.layer_maker(fam.global_shapes(c), fam.rules(c), SEED,
                               arch.num_layers, jnp.bfloat16)(-1)
    for k, v in glob.items():
        assert np.array_equal(np.asarray(params[k].astype(jnp.float32)),
                              np.asarray(v)), k


def test_seeds_past_32_bits_give_other_weights():
    a = weights.base_key(5)
    b = weights.base_key(5 + 2 ** 32)
    import jax
    assert not np.array_equal(np.asarray(jax.random.key_data(a)),
                              np.asarray(jax.random.key_data(b)))
