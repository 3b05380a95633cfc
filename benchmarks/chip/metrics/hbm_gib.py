"""Bytes the compiled chunk program needs on its fullest chip, in GiB:
arguments + outputs + temporaries - aliased, from the compiler's memory
analysis of the program the window drives."""


def read(ctx):
    return ctx.program_bytes / 2 ** 30 if ctx.program_bytes else None
