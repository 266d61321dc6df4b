"""Router capacity at decode: token-expert assignments kept, over the
E x C rows the decode GMM computes (C from the slot count and the
capacity factor), from the engine's load and overflow counters (%)."""
from bench import reference


def read(ctx):
    tr, m = ctx["run"].get("traced"), ctx["m"]
    if not tr or tr["decode_entries"] <= 0:
        return None
    rows = (m["n_experts"] * reference.capacity(tr["n_slots"], m)
            * ctx["arch"].moe_layers(m) * tr["decode_entries"])
    return 100.0 * tr["decode_kept"] / rows
