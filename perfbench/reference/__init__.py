"""perfbench.reference."""
