"""Pallas TPU kernels for the framework's perf-critical compute:

  flash_attention — GQA causal attention (dense/moe/vlm/encdec archs)
  rwkv6_scan      — WKV6 recurrence with data-dependent decay (rwkv6-7b)
  knn_topk        — row top-2 + regret for the paper's MIQP-NN projection

The paper itself has no kernel-level contribution (it is a scheduling
paper — DESIGN.md §3); these kernels serve the surrounding framework's
hot spots plus the paper's optimizer inner step.  Each ships a pure-jnp
oracle (ref.py), validated on the CPU in interpret=True mode; TPU is
the target, and knn_topk's compile for a v5e is checked in
tests/test_tpu_compile.py."""
