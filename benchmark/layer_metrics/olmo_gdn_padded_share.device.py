"""Share of the rule's kernel's state products (D x E a token and value
head) that multiply the zero columns its heads are laid in whole lane
tiles with: the program's own static gauge `biscotti_gdn_padded_share`
(`model.info["gdn_rule"]["padded_share"]`, from
`biscotti_tpu/ops/delta_rule.py:plan`): 0.4375 at heads of 96 | 192 in
128 | 256, 0 where nothing is padded or the rule is off the kernel. Work
the chip does and the model does not ask for: lower is better. None where
the traced model states no such share."""


def read(record):
    info = getattr(getattr(record.get("sim"), "model", None), "info", None)
    return ((info or {}).get("gdn_rule") or {}).get("padded_share")
