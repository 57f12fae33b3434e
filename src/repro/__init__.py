"""FedLuck reproduction: joint local-updating + gradient-compression AFL."""
