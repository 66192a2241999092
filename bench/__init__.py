"""The chip benchmark of this repository: harness, traffic, checks, trace reduction."""
