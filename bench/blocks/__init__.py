"""Blocks: one module per architecture the benchmark can judge.

A configuration file names its block with ``"block": "<name>"`` (none
named: ``llama``), and ``spec.load_block`` imports ``blocks/<name>.py``.
The harness reaches a configuration's architecture only through these
names, so a new architecture enters by a new block file:

- ``sizes(conf) -> dict``: the sizes read from the configuration file,
  ``vocab`` (the published vocabulary) among them;
- ``program_fields(sizes, conf) -> dict``: the program config's fields,
  ``family`` included, that must equal them;
- ``ONES`` and ``fan_in(name, sizes)``: the weight rule. A leaf named in
  ``ONES`` is filled with ones; any other is drawn N(0, fan_in^-0.5);
- ``logits(params, prompt, served, *, sizes, max_seq, window,
  quant=None)``: the plain float32 reference's logits at each served
  position (``quant="fp8"``: the control's), which ``compare.judge``
  holds what was served to;
- ``decode_flops(sizes, ctx_lengths)`` and ``paged_attn_cost(sizes,
  ctx_lengths)``: the model FLOPs of a decode step, and the FLOPs and
  bytes of its paged attention.
"""
