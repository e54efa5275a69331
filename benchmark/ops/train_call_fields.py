"""Op kind `train_call_fields`: `train_call` for a field-aware entry point.
One op is the same UDTF lifetime; the rows are handed over as the pre-hashed
`(idx_rows, val_rows, field_rows)`, three `[n, lanes]` arrays, where a lane's
field is its column, as `ffm_features` numbers a table's columns (Criteo:
0-12 the integer columns, 13-38 the categorical ones). The window, the kept
calls, `check` and the result's keys are `train_call`'s."""

from __future__ import annotations

import numpy as np

from . import train_call


class Op(train_call.Op):
    def _form(self, split, rows=None):
        ids, vals = super()._form(split, rows)
        fields = np.broadcast_to(
            np.arange(ids.shape[1], dtype=np.int32), ids.shape)
        return (ids, vals, fields)
