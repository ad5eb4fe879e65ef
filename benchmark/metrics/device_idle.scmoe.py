"""device_idle.scmoe (%): device_idle.dp's reading in the ScMoE decode
cell, where it shows whether the dense path hides the driver's host
path, and moves token_ms."""
import harness

read = harness.load_reader("device_idle.dp")
