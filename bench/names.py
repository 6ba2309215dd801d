"""Stable names the readers find in a profiler trace: the engine's jitted
programs (``XLA Modules``) and the Pallas kernels inside them
(``XLA Ops``).  A name that matches nothing makes the reader raise, and
its metric is left out of the result line."""

# serve/decode_loop.py make_fused_decode: jax.jit(fused)
DECODE_PROGRAM = r"^jit_fused$"
# serve/prefill.py _suffix_jit: jax.jit(f), the chunked paged prefill
PREFILL_PROGRAM = r"^jit_f$"
# kernels/paged_attention/kernel.py: the pallas_call in paged_attention_kernel
PAGED_ATTN_KERNEL = r"paged_attention_kernel"
