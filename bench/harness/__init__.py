"""The benchmark harness: one general path from a cell's files to its result."""
