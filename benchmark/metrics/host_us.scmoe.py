"""host_us.scmoe (us): host_us.dp's reading, per driver call of the
ScMoE layer's expert branch (submit -> dispatch), in the ScMoE decode
cell, where it moves token_ms."""
import harness

read = harness.load_reader("host_us.dp")
