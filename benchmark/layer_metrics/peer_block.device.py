"""Peers whose steps the round computes together: the program's own
`Simulator.peer_block` (gauge `biscotti_sim_peer_block`), which
`models/peer_step.py:peer_block` works out from the model's `step_bytes`
and the bytes the chip's runtime states, as the driver recorded it. The
21 sampled peers are walked in 21 / this many blocks, and every block
makes each sparse layer's grouped calls once: higher is fewer calls on
longer groups. Two PRs' records had a cell at 3 that ran 1 while no
metric read this."""


def read(record):
    return record.get("peer_block")
