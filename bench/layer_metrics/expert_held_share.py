"""Model step: of the router's pairs that chose a real expert, the share
computed here, over the window's prefills, from the program's own counters:
100 x ``routed_pairs`` / (``router_pairs`` - ``zero_pairs``). 3.125 = 16 / 512
under an even router where 16 of a layer's 512 experts are held; 100 if the
layer ever computes experts it does not hold (or holds them all). Lower is
nearer the deployment's share. None where the program counts no such pairs
(a block whose router chooses among the experts it holds)."""


def read(ctx):
    b = ctx["counters"]["batcher"]
    real = b.get("router_pairs", 0) - b.get("zero_pairs", 0)
    if real <= 0:
        return None
    return 100.0 * b.get("routed_pairs", 0) / real
