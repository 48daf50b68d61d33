"""Pallas TPU kernels for the zLLM storage layer.

Storage-path kernels (the paper's hot loops; pipeline context in
docs/ARCHITECTURE.md):
  bitx_xor.py     — fused XOR + byte-plane split/merge (BitX encode/decode)
  hamming.py      — fused XOR + popcount + two-stage reduce (bit distance)
  byte_planes.py  — ZipNN byte-plane shuffle (the no-family fallback)

Each kernel pairs with a pure-jnp oracle in ``ref.py``; ``ops.py`` is the
public jit'd API. On the CPU backend kernels run in interpret mode; tests
sweep shapes/dtypes asserting exact agreement with the oracles, and
tests/test_tpu_compile.py compiles the storage-path kernels for a described
TPU v5e.

These kernels are LIVE in the storage pipeline, reached through two layers
of indirection rather than called directly: the pipeline dispatches every
tensor to a codec via the registry in ``repro.core.codecs``
(``register_codec``; six lanes — bitx / bitxq / zipnn / raw / stored /
dedup), and each codec's encode/decode runs on the session's
``ArrayBackend``. The jax backend (``repro.core.bitx.JaxBackend``, selected
via ``ZLLMStore(backend="jax")`` or ``"auto"`` on accelerator hosts)
implements the backend primitives — ``xor_delta_planes``, ``byte_planes``,
``merge_planes_xor`` — on ``ops.bitx_encode_planes`` / ``bitx_decode_planes``
/ ``zipnn_split_planes`` / ``zipnn_merge_planes``, and the device-batched
hot path concatenates same-width tensors so each dtype bucket costs one
fused launch (the ``bitxq`` lane deliberately stays on the host path for
cross-backend determinism). Containers stay bit-identical to the numpy host
path (test-enforced), so the kernels are a pure throughput substitution.
"""
