"""Weights from the seed: the reference's layer-by-layer draw is bit for
bit the weights the program was given, and the seed decides them."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import weights  # noqa: E402


def tiny_abstract():
    from repro.configs import get_smoke
    from repro.models import build_model

    cfg = dataclasses.replace(get_smoke("granite_moe_3b_a800m"),
                              remat=False)
    return build_model(cfg).abstract()


def test_layer_draw_equals_the_served_weights():
    abstract = tiny_abstract()
    seed = 2**33 + 9
    params = weights.init_weights(abstract, seed)
    layers = weights.LayerWeights(abstract)
    for l in range(params["layers"]["attn"]["wq"].shape[0]):
        got = layers.draw(seed, l)
        for path, x in got.items():
            node = params["layers"]
            for k in path.split("/"):
                node = node[k]
            np.testing.assert_array_equal(
                np.asarray(node[l].astype(jnp.float32)), np.asarray(x))
    top = weights.top_weights(abstract, seed)
    np.testing.assert_array_equal(
        np.asarray(params["head"]["w"].astype(jnp.float32)),
        np.asarray(top["head/w"]))


def test_scales_follow_the_fan_in_and_the_seed_decides():
    abstract = tiny_abstract()
    a = weights.init_weights(abstract, 1)
    b = weights.init_weights(abstract, 2)
    wq = np.asarray(a["layers"]["attn"]["wq"], np.float32)   # (L, d, H, dh)
    assert abs(wq.std() * np.sqrt(wq.shape[1]) - 1) < 0.1
    wo = np.asarray(a["layers"]["attn"]["wo"], np.float32)   # (L, H, dh, d)
    assert abs(wo.std() * np.sqrt(wo.shape[1] * wo.shape[2]) - 1) < 0.1
    assert np.all(np.asarray(a["final_ln"]["scale"]) == 1)
    assert not np.array_equal(np.asarray(a["head"]["w"]),
                              np.asarray(b["head"]["w"]))
    assert jax.tree.structure(a) == jax.tree.structure(abstract)
