"""Model step: the share of the router's pairs that chose an identity
expert, over the window's prefills, from the program's own counters: 100 x
``zero_pairs`` / ``router_pairs`` (both of the real tokens, summed over the
expert layers). 33.3 under an even router over 512 experts and 256 identity
experts; 0 if identity picks are ever dropped, or taken for experts that lie
elsewhere. None where the program counts no such pairs (a block whose router
chooses among the experts it holds, a program without the counters)."""


def read(ctx):
    b = ctx["counters"]["batcher"]
    pairs, zero = b.get("router_pairs", 0), b.get("zero_pairs", 0)
    if not pairs:
        return None
    return 100.0 * zero / pairs
