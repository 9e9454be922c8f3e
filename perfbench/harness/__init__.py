"""The harness: traffic, the wall-clock pump, trace reduction, the check."""
