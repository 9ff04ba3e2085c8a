"""The part of ``step.mtp_ms`` in the module's pass over the vocabulary:
the shared head's product on the module's state, its cross-entropy and
their gradients, made where the logits are made (scope ``mtp_head`` inside
``part:loss``, part ``mtp_head``), per step run on chip 0. None where the
program has no such scope."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "mtp_head") or None
