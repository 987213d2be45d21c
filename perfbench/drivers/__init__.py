"""perfbench.drivers."""
