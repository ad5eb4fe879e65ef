"""device_idle.ep (%): device_idle.dp's reading in the EP cell, where it
moves token_ms."""
import harness

read = harness.load_reader("device_idle.dp")
