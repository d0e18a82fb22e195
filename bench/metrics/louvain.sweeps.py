"""Local-moving sweeps per solve, summed over levels
(``LouvainResult.sweeps_per_level``), mean over the window's solves."""


def read(run):
    per = [sum(s.answer.result.sweeps_per_level) for s in run.solves or ()
           if hasattr(s.answer.result, "sweeps_per_level")]
    return sum(per) / len(per) if per else None
