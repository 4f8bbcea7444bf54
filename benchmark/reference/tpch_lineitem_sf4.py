"""Plain reference for ``tpch_lineitem_sf4``: the verification metrics of
the table, computed with numpy and pyarrow.

The configuration is ``tpch_lineitem_sf1``'s battery at four times the
scale, so the reference is that configuration's verification reference;
how the program splits the work over chips does not enter it. ``dtype``
is the precision of the floating-point aggregates: float64 for the
reference, float32 for the control.
"""

from benchmark.reference.tpch_lineitem_sf1 import (  # noqa: F401
    compare_suite,
    control_suite,
    suite,
)
