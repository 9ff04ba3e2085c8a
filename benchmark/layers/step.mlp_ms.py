"""Device time of one train step inside the dense stacks, forward and backward
(part ``mlp``; a weight's optimizer update fused into its gradient matmul is
here too), per step run on chip 0."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "mlp")
