"""rec_rounds.single: rounds of the recovery replay's outer loop per
traced graph, as the program counts them (`SparsifyResult.loop_rounds`).
The traced calls' graphs are sent again through `lgrass_sparsify` after
the window; the pool is fixed, so the counts are the traced calls' own."""


def after_window(run):
    from repro.core import lgrass_sparsify

    rounds = {}
    for c in run.traced:
        if c.index not in rounds:
            graphs, budgets = run.pool[c.index]
            got = [getattr(lgrass_sparsify(g, budget=b), "loop_rounds",
                           None) for g, b in zip(graphs, budgets)]
            if not all(got):
                return
            rounds[c.index] = [r["rec"] for r in got]
    run.rec_rounds = [r for c in run.traced for r in rounds[c.index]]


def read(run):
    counts = getattr(run, "rec_rounds", None)
    return sum(counts) / len(counts) if counts else None
