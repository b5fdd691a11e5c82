"""The fullest held expert's token-expert assignments over the held
experts' mean, in the worst sparse layer: the program's own gauge
`biscotti_moe_load_max_over_mean` (`Simulator.dispatch_stats`, from the
counts the round's dispatch returns), median over the window's rounds.
1 is an even load; the grouped product's time follows the sum, its tail
the fullest group."""

import statistics


def read(record):
    values = (record.get("moe") or {}).get("load_max_over_mean")
    return statistics.median(values) if values else None
