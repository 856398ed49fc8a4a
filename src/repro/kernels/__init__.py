"""Pallas TPU kernels: changepoint (the paper's SSE scan), windowvet (the
fused block-sparse window-vet kernel), flash_attention, ssd.

Interpret-vs-compiled is a platform policy, not a hardcoded flag: for the
main-path kernels (changepoint, windowvet) ``runtime.resolve_interpret``
picks compiled on TPU and interpret mode on CPU; only an explicit
``interpret=`` argument overrides it.  The tests run interpret mode on CPU,
``tests/test_tpu_compile.py`` compiles the kernels for a described v5e, and
``chip_smoke.py`` runs them compiled on the chip."""
