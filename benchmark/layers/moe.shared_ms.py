"""The part of ``step.moe_ms`` inside the shared expert, the dense SwiGLU
every token goes through beside the routed experts, forward and backward
(scope ``moe/shared``, part ``moe_shared``), per step run on chip 0. None
where the program has no such scope."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "moe_shared") or None
