"""Op kinds: one module each, found by the traffic file's `op` key."""
