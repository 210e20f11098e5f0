from repro.roofline.hlo import analyze_hlo, HLOStats  # noqa: F401
