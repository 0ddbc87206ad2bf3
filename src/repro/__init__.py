"""GADGET reproduction: ring-all-reduce scheduling + executable RAR training."""
