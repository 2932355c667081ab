"""The benchmark of dlrover-tpu on the chip: see README.md."""
