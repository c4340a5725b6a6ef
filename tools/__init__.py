# Repo tooling package (makes `python -m tools.lint` work from the repo
# root). Scripts that predate the package (check_metric_names.py,
# soak.py) still run as plain files.
