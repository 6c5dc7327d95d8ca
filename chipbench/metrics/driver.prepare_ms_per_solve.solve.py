"""Host time inside the program's ``driver.prepare`` spans per answered
solve of the traced window, in milliseconds: each driver's work before it
dispatches its device loop (autotune lookup, validation of the box
fields, axis factors, the metric diagonal, operand casts)."""
import program_spans


def read(run):
    return program_spans.ms_per_solve(run, "driver.prepare")
