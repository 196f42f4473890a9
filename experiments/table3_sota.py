"""Table 3: comparison with state-of-the-art estimators (literature constants).

Prints the published SOTA numbers next to this reproduction's results (read
from the Table-1/Table-2 runs where available) -- sample count is the axis the
paper competes on.
"""

from __future__ import annotations

from experiments.common import emit

LITERATURE = [
    # work, type, platform, dataset size, rmspe, mape
    ("ANNETTE[11]", "conv2d-layer", "NCS2", 35000, "42.60%", "15.57%"),
    ("ANNETTE[11]", "conv2d-layer", "ZCU102", 35000, "10.55%", "12.71%"),
    ("ANNETTE[11]", "whole-dnn", "NCS2", 36570, "-", "7.44%"),
    ("ANNETTE[11]", "whole-dnn", "ZCU102", 37812, "-", "3.47%"),
    ("Blackthorn[7]", "conv2d-layer", "JetsonNano", 15000, "5.89%", "-"),
    ("Blackthorn[7]", "conv2d-layer", "JetsonTX2", 15000, "6.10%", "-"),
    ("Bouzidi[2]", "whole-dnn", "JetsonAGX", 200000, "-", "7.67%"),
    ("Bouzidi[2]", "whole-dnn", "JetsonTX2", 200000, "-", "8.37%"),
    ("nn-Meter[13]", "whole-dnn", "CortexA76", 15824, "2.76-5.54%", "-"),
    ("nn-Meter[13]", "whole-dnn", "Adreno640", 14040, "1.35-5.32%", "-"),
    ("nn-Meter[13]", "whole-dnn", "NCS2", 39968, "4.26-22.25%", "-"),
    ("paper(this)", "conv2d-layer", "Undisclosed", 9000, "9.93%", "7.35%"),
    ("paper(this)", "conv2d-layer", "JetsonAGX", 8000, "27.06%", "13.13%"),
    ("paper(this)", "whole-dnn", "Undisclosed", 9500, "4.53%", "2.90%"),
    ("paper(this)", "whole-dnn", "JetsonAGX", 9500, "20.17%", "19.60%"),
]


def main() -> None:
    for work, typ, platform, n, rmspe, mape in LITERATURE:
        emit(f"table3[{work}/{typ}/{platform}]", 0.0, f"n={n};rmspe={rmspe};mape={mape}")
    # our headline numbers are produced live by table1/table2 benchmarks;
    # point the reader there for apples-to-apples rows on this platform set
    emit("table3[repro]", 0.0, "see table1[*] and table2[*] rows (<=9000 PR samples)")


if __name__ == "__main__":
    main()
