"""Mean device ms of one run of the engine's jitted prefill step (one
chunk of one request)."""
from readers import program_ms


def read(ctx):
    return program_ms(ctx, "prefill")
