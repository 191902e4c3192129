"""The benchmark of perceiver-tpu: harness, traffic, reference and
yardsticks. Nothing here is imported by the program."""
