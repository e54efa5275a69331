"""Plain references: the same semantics as the entry points under test,
written from the learners' published update rules in numpy, on the touched
ids only (so they fit the host at any table size). They import nothing of
`hivemall_tpu`."""
