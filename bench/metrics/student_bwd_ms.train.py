"""Device time per QAD step of the student's backward
(``transpose(jvp(qad.student))``) in the traced window: the own time of
the ops whose HLO instruction carries that phase (``qad_phases``)."""
import qad_phases


def read(ctx):
    return qad_phases.read(ctx, "student_bwd")
