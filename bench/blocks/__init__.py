"""Blocks: what the benchmark does differently for each kind of model.

A configuration file names its block (``"block": "<name>"``), and the
harness takes everything that depends on the model's layers from
``bench/blocks/<name>.py`` (found by :func:`bench.registry.block`).  So a
configuration of a new architecture is added by files alone: its block
module, its configuration, its cells and its metrics.

A block module gives these functions; ``cfg`` is the configuration
file's contents, ``m`` is ``dims(cfg)``, and ``step`` is one stage
step's record as ``bench.run.instrument`` makes it (``stage``, ``kind``
"decode" or "chunk", ``batch``, ``tokens``, ``layers``, ``last``,
``ctx_sum``, ``rows_ctx_sum``, ``sampled``).

  dims(cfg) -> dict
      the widths the benchmark reads (hashable values: the reference
      passes them to jit as static arguments).
  make(cfg, seed) -> dict
      every weight as bfloat16 on the default device, in the block's own
      layout, the same for the same seed.  A block may draw leaf by leaf
      or layer by layer.
  program_model(cfg, kv_quant) -> (arch, model)
      the program's model at the file's widths; refuses an architecture
      the block's reference does not compute.
  program_params(model, w) -> tree
      the weights in the program's parameter tree, checked against
      ``model.abstract_params()`` (``bench.program.fit``).
  served_gaps(w, cfg, prompt, served) -> array
      the plain reference: per served token, how far its logit lies
      below the reference's best.  float32 under
      ``default_matmul_precision("highest")``, written from the layer
      equations; it imports nothing of the program.
  control_gaps(w, cfg, prompt, served) -> array
  control_decode(w, cfg, prompt, n) -> list
      the float8 control of the same reference: the tokens it puts first
      at the served positions, read as gaps; ``n`` greedy tokens.
  step_flops(m, step) -> int
      model FLOPs of a stage step: valid tokens only, the LM head on the
      sampled rows of the last stage.
  decode_bytes(m, step) -> int
      least HBM bytes of a decode step: the stage's weights and the K/V
      its rows' contexts need, each read once.
  span_attn_work(m, step) -> (flops, bytes)
      the least work of a chunk step's span attention.

The counts are of the mathematics, as :mod:`bench.flops` defines them.
"""

CONTRACT = ("dims", "make", "program_model", "program_params",
            "served_gaps", "control_gaps", "control_decode",
            "step_flops", "decode_bytes", "span_attn_work")
