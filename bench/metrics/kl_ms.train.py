"""Device time per QAD step of the losses over the 151936-wide
vocabulary, the KL's forward and backward (``qad.kl``), in the traced
window: the own time of the ops whose HLO instruction carries that phase
(``qad_phases``)."""
import qad_phases


def read(ctx):
    return qad_phases.read(ctx, "kl")
