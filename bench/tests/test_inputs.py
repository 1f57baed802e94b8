"""Inputs the benchmark makes from the seed: weights the program and the
reference see alike, and traffic whose work does not change with it."""

import json
from pathlib import Path

import jax
import numpy as np

from bench import traffic, weights

DATA = Path(__file__).resolve().parent / "data"
TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def test_program_and_reference_weights_agree():
    from repro.core.nestedfp import decode
    from repro.models.convert import serving_param_shapes
    import bench.run as R
    cfg = json.loads((DATA / "tiny.json").read_text())
    seed = 2**32 + 99
    tree = weights.serving_params(seed, cfg)
    want = serving_param_shapes(R.arch_config(cfg))
    got_s = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    want_s = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    assert jax.tree.structure(got_s) == jax.tree.structure(want_s)
    assert jax.tree.leaves(got_s) == jax.tree.leaves(want_s)
    draw = weights.layer_values(cfg)
    for layer in range(cfg["num_hidden_layers"]):
        ref = draw(weights.root_key(seed), layer)
        for name in weights.LINEARS:
            part, leaf = name.split("/")
            w = tree["layers"][part][leaf].weight
            f16 = decode(w.upper[layer], w.lower[layer])
            np.testing.assert_array_equal(np.asarray(f16, np.float32),
                                          np.asarray(ref[name]))
        np.testing.assert_array_equal(tree["layers"]["ln1"][layer],
                                      ref["ln1"])
        np.testing.assert_array_equal(
            tree["layers"]["attn"]["wq"].bias[layer], ref["attn/wq/b"])
    glob = weights.global_values(seed, cfg)
    np.testing.assert_array_equal(tree["embed"]["tok"], glob["embed"])
    np.testing.assert_array_equal(tree["lm_head"]["w"], glob["lm_head"])


def test_every_seed_gets_the_same_work():
    for path in [*TRAFFIC.glob("*.json"), DATA / "tiny_mix.json"]:
        mix = json.loads(path.read_text())
        runs = [traffic.generate(mix, s, 30.0, 1000) for s in (1, 2**40 + 3)]
        # the same schedule: due times and lengths, in the same order
        assert [(x.due_s, len(x.prompt), x.max_new) for x in runs[0]] == \
            [(x.due_s, len(x.prompt), x.max_new) for x in runs[1]], path.name
        # the seed draws the token ids
        assert runs[0][0].prompt != runs[1][0].prompt
        # and the seed alone: the same seed gives the same inputs
        again = traffic.generate(mix, 1, 30.0, 1000)
        assert [x.prompt for x in again] == [x.prompt for x in runs[0]]
        dues = [x.due_s for x in runs[0]]
        assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 30.0
        for x in runs[0]:
            assert mix["prompt"]["min"] <= len(x.prompt) <= mix["prompt"]["max"]
            assert mix["output"]["min"] <= x.max_new <= mix["output"]["max"]
