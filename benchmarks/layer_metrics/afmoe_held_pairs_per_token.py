"""The (token, expert) pairs this chip's held experts computed a token,
all expert layers together, the window's steps averaged: the rows the
grouped products took (`held_pairs`, which every step reports) over the
step's tokens.  Its expectation is expert layers x top-k x held /
router's experts (4 at 4 layers of 8 x 16 / 128)."""
LAYER, UNIT, SOURCE, MOVES = "models", "pairs/token", "program_counter", "train_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._train_window_moe_common import mine

    t = mine(ctx)
    xs = ((t or {}).get("step_metrics") or {}).get("held_pairs") or []
    if not xs or not t.get("tokens_per_step"):
        return None
    return sum(xs) / len(xs) / t["tokens_per_step"]
