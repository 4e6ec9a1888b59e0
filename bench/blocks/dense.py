"""The dense decoder block: pre-RMSNorm GQA attention with rotary
positions and a SwiGLU MLP on every layer, one array per kind of matrix
stacked over layers.  Its weights, program mapping, reference and work
counts are :mod:`bench.weights`, :mod:`bench.program`,
:mod:`bench.reference` and :mod:`bench.flops`."""
from __future__ import annotations

from bench import flops, program, reference, weights
from bench.blocks import CONTRACT

__all__ = list(CONTRACT)

dims = weights.dims
make = weights.make
program_params = program.program_params
served_gaps = reference.served_gaps
control_gaps = reference.control_gaps
control_decode = reference.control_decode


def program_model(cfg: dict, kv_quant: bool = False):
    """The program's model; an architecture with a window or experts is
    refused, since the reference computes neither."""
    arch = program.arch_config(cfg)
    if arch.family != "dense" or arch.window or arch.moe is not None:
        raise ValueError(f"{cfg['name']}: the dense block's reference "
                         f"covers the dense block only, not {arch.family}"
                         f" (window {arch.window}, moe {arch.moe})")
    return program.build_model(cfg, kv_quant=kv_quant)


def step_flops(m: dict, step: dict) -> int:
    return flops.step_flops(m, step["layers"], step["tokens"],
                            step["ctx_sum"],
                            step["sampled"] if step["last"] else 0)


def decode_bytes(m: dict, step: dict) -> int:
    return (flops.stage_weight_bytes(m, step["layers"], step["last"])
            + flops.kv_bytes(m, step["layers"], step["rows_ctx_sum"]))


def span_attn_work(m: dict, step: dict) -> tuple:
    return (step["layers"] * flops.attn_flops(m, step["ctx_sum"]),
            flops.span_attn_bytes(m, step["layers"], step["tokens"],
                                  step["rows_ctx_sum"]))
