"""rec_width_share.single: the share of cover-table columns the recovery
replay's blocks held, against tables as wide as the whole accept buffer
in every block: 100 x sum(rec_table_cols) / sum(rounds x (b_cap + C)),
over the traced graphs. The program counts the columns
(`SparsifyResult.rec_table_cols`) and the blocks (`loop_rounds["rec"]`);
b_cap is `lgrass_sparsify`'s bucket of the graph's budget and C its
replay block. The traced calls' graphs are sent again through
`lgrass_sparsify` after the window; the pool is fixed, so the counts are
the traced calls' own. A program without the column count reads None."""

CHUNK = 32  # lgrass_sparsify's default replay block


def after_window(run):
    from repro.core import lgrass_sparsify
    from repro.core.sparsify import _bucket_b_cap

    per = {}
    for c in run.traced:
        if c.index in per:
            continue
        graphs, budgets = run.pool[c.index]
        per[c.index] = []
        for g, b in zip(graphs, budgets):
            r = lgrass_sparsify(g, budget=b)
            held = getattr(r, "rec_table_cols", None)
            if held is None:
                return
            full = r.loop_rounds["rec"] * (_bucket_b_cap([b])
                                           + max(min(CHUNK, g.m), 1))
            per[c.index].append((held, full))
    run.rec_width = [x for c in run.traced for x in per[c.index]]


def read(run):
    got = getattr(run, "rec_width", None)
    full = sum(f for _, f in got) if got else 0
    return 100.0 * sum(h for h, _ in got) / full if full else None
