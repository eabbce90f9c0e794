"""The benchmark's harness: discovery, traffic, trace reduction, costs."""
