"""Operations and bytes of the work, from shapes alone.

These count what the algorithm needs, not what an implementation does,
so they stay the same when a kernel changes:

  * a GEMM of (M, K) by (K, N) is 2*M*K*N operations; it reads the
    weight (2 bytes an element in FP16 mode, 1 in FP8 mode, where only
    the upper byte plane is read), the activation (2 or 1 bytes an
    element: f16 values or e4m3 values) and writes an f32 result;
  * one token at context length c (itself included) costs the linear
    GEMMs of every layer, 4*c*heads*head_dim operations of attention per
    layer (scores and weighted values), and, where its logits are taken,
    the LM head (2*d_model*vocab).

The linears and their shapes are the configuration's family module's
(`bench/family.py`). On a mesh of `chips` chips the work is split over
them: a roofline time is that of each chip's even share (operations and
bytes over `chips`), which no chip can beat, since an even share is the
least the busiest chip can hold.
"""

from __future__ import annotations

from bench import family

WEIGHT_BYTES = {"fp16": 2, "fp8": 1}
ACT_BYTES = {"fp16": 2, "fp8": 1}
OUT_BYTES = 4


def gemm(m: int, k: int, n: int, mode: str) -> tuple[int, int]:
    """(operations, bytes) of one nested GEMM call."""
    flops = 2 * m * k * n
    nbytes = (k * n * WEIGHT_BYTES[mode] + m * k * ACT_BYTES[mode]
              + m * n * OUT_BYTES)
    return flops, nbytes


def roofline_s(flops: int, nbytes: int, peak: dict, chips: int = 1) -> float:
    """The least time a chip could take for its even share of the work
    over `chips` chips: the larger of the compute and the memory bound."""
    return max(flops / chips / peak["flops_per_s"],
               nbytes / chips / peak["hbm_bytes_per_s"])


def step_gemm_roofline_s(cfg: dict, m: int, mode: str, peak: dict,
                         chips: int = 1) -> float:
    """Roofline time of every nested GEMM of one step program over m rows
    (all layers), for each chip's even share over `chips` chips."""
    per_layer = sum(roofline_s(*gemm(m, k, n, mode), peak, chips)
                    for k, n in family.of(cfg).linear_shapes(cfg).values())
    return per_layer * cfg["num_hidden_layers"]


def linear_flops_per_token(cfg: dict) -> int:
    return 2 * sum(k * n for k, n in
                   family.of(cfg).linear_shapes(cfg).values()) \
        * cfg["num_hidden_layers"]


def attention_flops(cfg: dict, start: int, stop: int) -> int:
    """Attention operations of the tokens at positions start..stop-1
    (context p + 1 each), over all layers."""
    ctx = (stop * (stop + 1) - start * (start + 1)) // 2
    return 4 * ctx * cfg["num_attention_heads"] * cfg["head_dim"] \
        * cfg["num_hidden_layers"]


def head_flops(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def chunk_flops(cfg: dict, start: int, take: int) -> int:
    """Model operations of a prefill chunk of `take` tokens from position
    `start`, with logits taken for its last token."""
    return (take * linear_flops_per_token(cfg)
            + attention_flops(cfg, start, start + take) + head_flops(cfg))


def decode_flops(cfg: dict, position: int) -> int:
    """Model operations of one decoded token written at `position`."""
    return (linear_flops_per_token(cfg)
            + attention_flops(cfg, position, position + 1) + head_flops(cfg))
