"""Convert trained params into NestedFP serving params.

Follows the paper's scope: NestedFP applies to *linear layers* (QKV/O,
MLPs, MoE expert banks, SSM/MLA projections). Embeddings, the LM head,
MoE routers, norms, convs and other 1-D params stay in their original
precision ("Quantization is applied exclusively to linear layers, with
embedding layers left in higher precision", paper §2.2/Table 1 note).

`structural=True` builds the same tree from ShapeDtypeStructs (no data,
applicability assumed) — used by the dry-run's input_specs() and the
compile rehearsals. `init_serving_params` builds seeded random serving
params on the device(s), sharded when asked.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.linear import NestedLinearParams
from repro.core.nestedfp import NestedTensor, encode, is_applicable

# path substrings excluded from nesting
_EXCLUDE = ("embed", "lm_head", "router", "frontend_proj")
# 3-D expert-bank / projection leaves nested as whole tensors
_BANK_KEYS = ("w_gate", "w_up", "w_down")


def _is_linear_dict(node) -> bool:
    return (isinstance(node, dict) and "w" in node
            and hasattr(node["w"], "ndim") and node["w"].ndim >= 2)


def _nest_structural(arr) -> NestedTensor:
    return NestedTensor(upper=jax.ShapeDtypeStruct(arr.shape, jnp.uint8),
                        lower=jax.ShapeDtypeStruct(arr.shape, jnp.uint8),
                        raw=None)


def _nest_host(arr) -> NestedTensor:
    return NestedTensor.from_f16(jnp.asarray(arr, jnp.float16))


def _convert(tree, nest, path: str = ""):
    excluded = any(e in path for e in _EXCLUDE)
    if isinstance(tree, dict):
        if _is_linear_dict(tree) and not excluded:
            return NestedLinearParams(weight=nest(tree["w"]),
                                      bias=tree.get("b"))
        out = {}
        for k, v in tree.items():
            if k in _BANK_KEYS and not excluded and hasattr(v, "ndim"):
                out[k] = nest(v)
            else:
                out[k] = _convert(v, nest, f"{path}/{k}")
        return out
    if isinstance(tree, (list, tuple)):
        return type(tree)(_convert(v, nest, f"{path}[{i}]")
                          for i, v in enumerate(tree))
    return tree


def to_serving(tree, *, structural: bool = False):
    """Recursively nest every eligible linear weight (applicability
    decided per tensor on the host; inapplicable ones stay f16 exception
    tensors)."""
    return _convert(tree, _nest_structural if structural else _nest_host)


def init_serving_params(key, cfg, shardings=None):
    """Seeded random serving params built in ONE jitted program: f32
    init, f16 rounding and nested encoding never leave the device(s), and
    with `shardings` (`serving.shard.serving_param_shardings` of
    `serving_param_shapes(cfg)`) each chip computes only its own slice —
    an 8B model's f32 params never exist on one chip. The tree and the
    distribution are those of `to_serving(model.init_params(key, cfg))`;
    values may differ in the last f32 bit, where XLA fuses the init.
    Raises if a linear is not nestable (no exception tensor is built
    here)."""
    from repro.models import model as M

    def build(k):
        nestable = []

        def nest(arr):
            w = arr.astype(jnp.float16)
            nestable.append(is_applicable(w))
            upper, lower = encode(w)
            return NestedTensor(upper=upper, lower=lower, raw=None)
        sparams = _convert(M.init_params(k, cfg), nest)
        return sparams, jnp.all(jnp.stack(nestable))

    out_sh = None if shardings is None else (shardings, None)
    sparams, ok = jax.jit(build, out_shardings=out_sh)(key)
    if not bool(ok):
        raise ValueError(f"{cfg.arch_id}: a linear weight has |w| > 1.75; "
                         "build it with to_serving (exception tensors)")
    return sparams


def serving_param_shapes(cfg):
    """ShapeDtypeStruct tree of `init_serving_params(key, cfg)`."""
    from repro.models import model as M
    return to_serving(jax.eval_shape(lambda k: M.init_params(k, cfg),
                                     jax.random.PRNGKey(0)), structural=True)


def serving_memory_bytes(tree) -> dict[str, int]:
    """Audit: bytes of nested vs. raw leaves (paper's zero-overhead claim)."""
    nested = raw = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "nbytes"):
            if leaf.dtype == jnp.uint8:
                nested += leaf.nbytes
            else:
                raw += leaf.nbytes
    return {"nested_bytes": nested, "other_bytes": raw,
            "total_bytes": nested + raw}
