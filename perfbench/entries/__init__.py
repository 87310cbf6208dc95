"""One module per estimator entry: how the benchmark hands it data, runs one
fit, reads its answer and its counters, and names the work a fit requires."""
