"""The on-chip serving benchmark's own code: spec loading, traffic
helpers, weights, the serving window, the float32 reference, trace
reduction and the operation and byte counts."""
