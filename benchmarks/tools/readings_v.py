"""``tools/readings.py`` with the fault of ``lib/controls_v.py`` known by
name beside the others:

    python3 benchmarks/tools/readings_v.py --workload sd21.edit-replace \
        --seeds 11 --control-seeds 11 --fault no_edit altered_answer epsilon_for_v
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import controls, controls_v  # noqa: E402
from benchmarks.tools import readings  # noqa: E402

if __name__ == "__main__":
    controls.FAULTS.update(controls_v.FAULTS)
    sys.exit(readings.main())
