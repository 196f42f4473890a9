PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test lint paper-smoke quickstart

test:
	$(PYTHON) -m pytest -x -q

lint:
	$(PYTHON) -m repro.analysis src

paper-smoke:
	$(PYTHON) -m experiments.run table1 fig2

quickstart:
	$(PYTHON) examples/quickstart.py
