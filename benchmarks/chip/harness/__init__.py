"""The on-chip serving benchmark's own code: spec loading, traffic
helpers, what every block shares (the seed's key, the layout check, the
reference's float32 and fp8 matmuls, counting arithmetic), the serving
window and trace reduction. What belongs to one architecture is in
`blocks/<name>.py`."""
