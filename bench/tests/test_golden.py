"""Nothing the cells read moves when the architecture moves into the
family module: the serving tree, the reference's weights and the
reference's gaps of the `tiny` configuration are bit for bit what the
harness drew and computed before the move (digests taken from the parent
tree, where `bench/weights.py` held the dense GQA leaves and tree)."""

import hashlib
import json
from pathlib import Path

import jax
import numpy as np

from bench import family, weights

DATA = Path(__file__).resolve().parent / "data"
SEED = 2**32 + 2024
# fixed (prompt, served tokens) sequences in the tiny vocabulary
SEQS = [([(7 * i + 3) % 512 for i in range(20)],
         [(11 * i + 5) % 512 for i in range(6)]),
        ([(13 * i + 1) % 512 for i in range(57)],
         [(5 * i + 9) % 512 for i in range(9)])]

TREE = {
    "['embed']['tok']":
        '158d4698a309dae1:float32[512, 256]',
    "['final_norm']":
        'f701d1ad7b7886b5:float32[256]',
    "['layers']['attn']['wk'][<flat index 0>][<flat index 0>]":
        'c3b37061ecacdff1:uint8[2, 256, 128]',
    "['layers']['attn']['wk'][<flat index 0>][<flat index 1>]":
        'f0aa49cb83d286e0:uint8[2, 256, 128]',
    "['layers']['attn']['wk'][<flat index 1>]":
        '2566603e9c166d29:float32[2, 128]',
    "['layers']['attn']['wo'][<flat index 0>][<flat index 0>]":
        '04a09ecc7f8fd0ac:uint8[2, 256, 256]',
    "['layers']['attn']['wo'][<flat index 0>][<flat index 1>]":
        'b3f090bf709e00b6:uint8[2, 256, 256]',
    "['layers']['attn']['wq'][<flat index 0>][<flat index 0>]":
        '2cd9391cae4e02e9:uint8[2, 256, 256]',
    "['layers']['attn']['wq'][<flat index 0>][<flat index 1>]":
        'fc4100341acf60c4:uint8[2, 256, 256]',
    "['layers']['attn']['wq'][<flat index 1>]":
        '20aa2729078d243a:float32[2, 256]',
    "['layers']['attn']['wv'][<flat index 0>][<flat index 0>]":
        'a7a1550f9b4a9bb4:uint8[2, 256, 128]',
    "['layers']['attn']['wv'][<flat index 0>][<flat index 1>]":
        '640781f0415a660a:uint8[2, 256, 128]',
    "['layers']['attn']['wv'][<flat index 1>]":
        'aa412c69f1aa2237:float32[2, 128]',
    "['layers']['ln1']":
        '4c8e1e88b31557f8:float32[2, 256]',
    "['layers']['ln2']":
        '66a757d3dea2c39a:float32[2, 256]',
    "['layers']['mlp']['down'][<flat index 0>][<flat index 0>]":
        '4ead6d1733416eed:uint8[2, 512, 256]',
    "['layers']['mlp']['down'][<flat index 0>][<flat index 1>]":
        'ec9b972f0c9f1a03:uint8[2, 512, 256]',
    "['layers']['mlp']['gate'][<flat index 0>][<flat index 0>]":
        '89c2167d3d118d1d:uint8[2, 256, 512]',
    "['layers']['mlp']['gate'][<flat index 0>][<flat index 1>]":
        'b1a40890fb0174b2:uint8[2, 256, 512]',
    "['layers']['mlp']['up'][<flat index 0>][<flat index 0>]":
        'f0060ac1c81ac876:uint8[2, 256, 512]',
    "['layers']['mlp']['up'][<flat index 0>][<flat index 1>]":
        '9da432dcca0d07d3:uint8[2, 256, 512]',
    "['lm_head']['w']":
        '6b3cb14c3b2a3d93:float32[256, 512]',
}

LAYERS = {
    '0:attn/wk':
        '0dde46b2e897c212:float32[256, 128]',
    '0:attn/wk/b':
        'a9842574ee2256bf:float32[128]',
    '0:attn/wo':
        '96f00b6c141683cd:float32[256, 256]',
    '0:attn/wq':
        '46201c1d18d9bf82:float32[256, 256]',
    '0:attn/wq/b':
        '27b63aff77b3533c:float32[256]',
    '0:attn/wv':
        'a84876346e37ec1f:float32[256, 128]',
    '0:attn/wv/b':
        'e55ee5a528f07dd7:float32[128]',
    '0:ln1':
        '042d5954681b8f50:float32[256]',
    '0:ln2':
        'a74cdda82d4a76ff:float32[256]',
    '0:mlp/down':
        '99a3e32c4b0caf43:float32[512, 256]',
    '0:mlp/gate':
        'c067749d6328fb04:float32[256, 512]',
    '0:mlp/up':
        '437a5202905f5a0f:float32[256, 512]',
    '1:attn/wk':
        'c79e48f140a766b9:float32[256, 128]',
    '1:attn/wk/b':
        '4ba44d56b41f616b:float32[128]',
    '1:attn/wo':
        'd1e12a45a47d3cb1:float32[256, 256]',
    '1:attn/wq':
        '1f9d9f8f77f98e05:float32[256, 256]',
    '1:attn/wq/b':
        '6eac83ca804824c5:float32[256]',
    '1:attn/wv':
        '085696eb1865fb01:float32[256, 128]',
    '1:attn/wv/b':
        '4c67146b385a029d:float32[128]',
    '1:ln1':
        'ad3fa0defd0d3c66:float32[256]',
    '1:ln2':
        '645cb9044d5bbe89:float32[256]',
    '1:mlp/down':
        'eefacd7b34d8cb80:float32[512, 256]',
    '1:mlp/gate':
        'faf50f435d4cd073:float32[256, 512]',
    '1:mlp/up':
        '0b9f485b4101ba21:float32[256, 512]',
}

GLOBALS = {
    'embed':
        '158d4698a309dae1:float32[512, 256]',
    'final_norm':
        'f701d1ad7b7886b5:float32[256]',
    'lm_head':
        '6b3cb14c3b2a3d93:float32[256, 512]',
}

# served_gaps(SEED, tiny, SEQS), as float.hex of each gap
GAPS = [
    ['0x1.cb26880000000p+1', '0x1.fc52480000000p+1', '0x1.f3c1d00000000p-1', '0x1.2f71f20000000p+1', '0x1.8bfa0e0000000p+0', '0x1.fd94800000000p+1'],
    ['0x1.ab5ce20000000p+1', '0x1.40e4140000000p+1', '0x1.72979c0000000p+1', '0x1.2bc4a40000000p+2', '0x1.034b5c0000000p+2', '0x1.862a200000000p+1', '0x1.edd2000000000p+1', '0x1.478dda0000000p+0', '0x1.7c73d00000000p+1'],
]


def digest(a) -> str:
    a = np.asarray(a)
    return (hashlib.sha256(a.tobytes()).hexdigest()[:16]
            + f":{a.dtype}{list(a.shape)}")


def tiny():
    return json.loads((DATA / "tiny.json").read_text())


def test_serving_tree_is_unchanged():
    tree = weights.serving_params(SEED, tiny())
    got = {jax.tree_util.keystr(p): digest(x)
           for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert got == TREE


def test_reference_weights_are_unchanged():
    cfg = tiny()
    fam = family.of(cfg)
    draw = fam.layer_values(cfg)
    got = {f"{layer}:{name}": digest(v)
           for layer in range(cfg["num_hidden_layers"])
           for name, v in sorted(draw(weights.root_key(SEED), layer).items())}
    assert got == LAYERS
    assert {n: digest(v) for n, v in
            sorted(fam.global_values(SEED, cfg).items())} == GLOBALS


def test_reference_gaps_are_unchanged():
    cfg = tiny()
    gaps = family.of(cfg).served_gaps(SEED, cfg, SEQS)
    assert [[float(x).hex() for x in g] for g in gaps] == GAPS
