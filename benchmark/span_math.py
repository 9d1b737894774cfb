"""Arithmetic over the idle gaps as `reduce.attribute_gaps` names them
(`trace["idle_gaps"]`: [[host span or pool name, idle seconds]]): how
much of the device's idle time falls under spans the program opens
itself, so that a gap reads in the program's terms and not under the
benchmark's own outermost span."""

SHORT_PAUSES = "pauses_under_50_us_between_operations"


def idle_in_spans_share(idle_gaps, prefixes):
    """Percent of the idle seconds, short pauses between operations
    left out, that lie under a span whose name starts with one of
    `prefixes`. None where nothing but short pauses was idle."""
    rows = [(name, s) for name, s in idle_gaps if name != SHORT_PAUSES]
    whole = sum(s for _name, s in rows)
    if not whole:
        return None
    inside = sum(s for name, s in rows if name.startswith(tuple(prefixes)))
    return 100.0 * inside / whole
