"""On-chip benchmark of the FSL-GAN round: harness, yardstick and cells."""
