"""Device-side ops: segment reductions, set-union ops, attention kernels, and
the one rule that picks a kernel or its plain form (``dispatch``)."""
