"""The benchmark of eges-tpu: harness, traffic, reference, readers."""
