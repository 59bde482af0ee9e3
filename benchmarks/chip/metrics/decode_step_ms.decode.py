"""Mean device ms of one run of the engine's jitted decode step."""
from readers import program_ms


def read(ctx):
    return program_ms(ctx, "decode")
