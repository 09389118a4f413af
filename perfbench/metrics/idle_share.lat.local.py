"""Device idle share (%) in the latency group's traced slice, as
``idle_share.lat`` reads it, for the cells that report
``lat_p50_us.local``."""

from perfbench import harness

read = harness.load_module("metrics", "idle_share.lat").read
