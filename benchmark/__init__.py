"""The benchmark of llmss-tpu: see benchmark/README.md."""
