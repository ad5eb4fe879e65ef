"""host_us.ep (us): host_us.dp's reading, per driver call of the MoE
layer, in the EP cell, where it moves token_ms."""
import harness

read = harness.load_reader("host_us.dp")
