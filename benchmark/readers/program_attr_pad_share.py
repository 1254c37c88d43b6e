"""1 - real / padded of two counts the program set on its own spans of one
name (those that ended in the window), in %: the share of a padded axis that
carries no real entry, counted where the batch is built."""

from harness import spec


def read(ctx, span: str, real: str, padded: str):
    found = spec.load_module("readers", "program_ring").spans(ctx, [span])
    total = sum(s.attrs.get(padded, 0) for s in found or ())
    if not total:
        return None
    return 100.0 * (1.0 - sum(s.attrs.get(real, 0) for s in found) / total)
