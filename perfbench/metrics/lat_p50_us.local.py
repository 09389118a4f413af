"""Median call-to-ready time (us) over every call of the latency group,
as ``lat_p50_us`` reads it, for a cell on one chip: there the host's
run-to-run spread is about twice the four-chip cells', and this metric
has a bound of its own."""

from perfbench import harness

read = harness.load_module("metrics", "lat_p50_us").read
