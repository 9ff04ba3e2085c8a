"""Device time of one train step spent making block diffusion's pair: the
one draw of a noise level a block and a mask a token, the select that puts
the mask id in, the 2·S ids and their positions, the weights the loss
takes (scope ``noise``, part ``noise``), per step run on chip 0. It has no
backward. None where the program has no such scope."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "noise")
