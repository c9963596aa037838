"""What every cell shares: arguments, devices, traffic, counts, trace reading."""
