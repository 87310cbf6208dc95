"""The benchmark: one command, cells and metrics as data (see README.md)."""
