"""Host time inside the program's ``driver.validate`` spans (the check
of the caller's mask and weight against the structured box,
``core/cg_fused._check_box_fields``) per answered solve of the traced
window, in milliseconds: the part of ``driver.prepare`` spent there."""
import program_spans


def read(run):
    return program_spans.ms_per_solve(run, "driver.validate")
