"""Model step: how unevenly the router fills the experts in prefill. The
fullest expert's rows over the mean rows an expert gets, averaged over the
window's prefills and expert layers, from the program's own counters:
``expert_load_max`` (per prefill and layer the fullest expert's pairs,
summed) x experts / ``routed_pairs``. 1 is an even router; the grouped
product's longest run, and an expert-parallel deployment's slowest chip,
grow with it. None where the program counts no pairs."""


def read(ctx):
    b = ctx["counters"]["batcher"]
    pairs, fullest = b.get("routed_pairs", 0), b.get("expert_load_max", 0)
    experts = ctx["dec_sizes"].get("experts")
    if not pairs or not fullest or not experts:
        return None
    return fullest * experts / pairs
