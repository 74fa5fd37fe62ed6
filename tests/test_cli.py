"""fjt-score CLI (flink_jpmml_tpu/cli.py): CSV + JSONL in, JSONL
predictions out, parity with score_records, stdin/stdout plumbing."""

import json
import pathlib

import numpy as np
import pytest

from flink_jpmml_tpu.api import ModelReader
from flink_jpmml_tpu.assets_gen import gen_iris_lr
from flink_jpmml_tpu.cli import score_main


@pytest.fixture()
def iris(tmp_path):
    return gen_iris_lr(str(tmp_path))


def _write_inputs(tmp_path, fields, rows):
    csv_p = pathlib.Path(tmp_path, "in.csv")
    lines = [",".join(fields)]
    for row in rows:
        lines.append(",".join("" if v is None else str(v) for v in row))
    csv_p.write_text("\n".join(lines) + "\n")
    jsonl_p = pathlib.Path(tmp_path, "in.jsonl")
    jsonl_p.write_text(
        "\n".join(
            json.dumps({f: v for f, v in zip(fields, row) if v is not None})
            for row in rows
        )
        + "\n"
    )
    return str(csv_p), str(jsonl_p)


class TestScoreCli:
    def test_csv_and_jsonl_match_api(self, tmp_path, iris):
        cm = ModelReader(iris).load()
        fields = list(cm.field_space.fields)
        rng = np.random.default_rng(3)
        rows = [
            [round(float(v), 4) for v in rng.normal(3, 2, len(fields))]
            for _ in range(20)
        ]
        rows[5] = [None] * len(fields)  # all-missing record → empty lane
        csv_p, jsonl_p = _write_inputs(tmp_path, fields, rows)

        recs = [
            {f: v for f, v in zip(fields, row) if v is not None}
            for row in rows
        ]
        ref = cm.score_records(recs)

        for inp in (csv_p, jsonl_p):
            out_p = str(pathlib.Path(tmp_path, "out.jsonl"))
            rc = score_main([iris, inp, "-o", out_p])
            assert rc == 0
            got = [
                json.loads(ln)
                for ln in pathlib.Path(out_p).read_text().splitlines()
            ]
            assert len(got) == len(ref)
            for g, r in zip(got, ref):
                if r.is_empty:
                    assert g == {"empty": True}
                else:
                    assert g["value"] == pytest.approx(
                        r.score.value, rel=1e-6
                    )
                    assert g["label"] == r.target.label
                    assert g["probs"][r.target.label] == pytest.approx(
                        r.target.probabilities[r.target.label], abs=2e-6
                    )

    def test_replace_nan_fills_numeric_fields(self, tmp_path, iris):
        cm = ModelReader(iris).load()
        fields = list(cm.field_space.fields)
        rows = [[None] * len(fields), [1.0] + [None] * (len(fields) - 1)]
        csv_p, _ = _write_inputs(tmp_path, fields, rows)
        out_p = str(pathlib.Path(tmp_path, "out.jsonl"))
        assert score_main(
            [iris, csv_p, "-o", out_p, "--replace-nan", "0.0"]
        ) == 0
        got = [
            json.loads(ln)
            for ln in pathlib.Path(out_p).read_text().splitlines()
        ]
        # with replacement nothing is empty, and row 0 == all-zeros record
        assert all("empty" not in g for g in got)
        ref = cm.score_records([{f: 0.0 for f in fields}])[0]
        assert got[0]["value"] == pytest.approx(ref.score.value, rel=1e-6)

    def test_stdin_jsonl(self, tmp_path, iris, monkeypatch, capsys):
        import io
        import sys

        cm = ModelReader(iris).load()
        fields = list(cm.field_space.fields)
        rec = {f: 2.0 for f in fields}
        monkeypatch.setattr(
            sys, "stdin", io.StringIO(json.dumps(rec) + "\n")
        )
        assert score_main([iris, "-"]) == 0
        out = capsys.readouterr().out.strip().splitlines()
        assert len(out) == 1
        ref = cm.score_records([rec])[0]
        assert json.loads(out[0])["value"] == pytest.approx(
            ref.score.value, rel=1e-6
        )

    def test_invalid_jsonl_is_typed_exit(self, tmp_path, iris):
        bad = pathlib.Path(tmp_path, "bad.jsonl")
        bad.write_text("{not json}\n")
        with pytest.raises(SystemExit, match="invalid JSON"):
            score_main([iris, str(bad)])

    def test_missing_files_are_typed_exits(self, tmp_path, iris):
        with pytest.raises(SystemExit, match="cannot read"):
            score_main([iris, str(tmp_path / "nope.csv")])
        good = pathlib.Path(tmp_path, "ok.jsonl")
        good.write_text("{}\n")
        with pytest.raises(SystemExit, match="cannot write"):
            score_main([iris, str(good), "-o",
                        str(tmp_path / "no" / "dir" / "out.jsonl")])

    def test_csv_numeric_looking_categoricals_ride_the_codec(self, tmp_path):
        # a CSV cell "2" for a string-categorical field must stay a
        # string: float-parsing it would bypass the codec and alias onto
        # the wrong category code
        xml = """<PMML version="4.3"><DataDictionary>
          <DataField name="c" optype="categorical" dataType="string">
            <Value value="1"/><Value value="2"/><Value value="3"/>
          </DataField>
          <DataField name="y" optype="continuous" dataType="double"/>
          </DataDictionary>
          <RegressionModel functionName="regression">
          <MiningSchema><MiningField name="y" usageType="target"/>
            <MiningField name="c"/></MiningSchema>
          <RegressionTable intercept="0.0">
            <CategoricalPredictor name="c" value="1" coefficient="10"/>
            <CategoricalPredictor name="c" value="2" coefficient="20"/>
            <CategoricalPredictor name="c" value="3" coefficient="30"/>
          </RegressionTable></RegressionModel></PMML>"""
        model = pathlib.Path(tmp_path, "cat.pmml")
        model.write_text(xml)
        csv_p = pathlib.Path(tmp_path, "in.csv")
        csv_p.write_text("c\n2\n3\n")
        out_p = str(pathlib.Path(tmp_path, "out.jsonl"))
        assert score_main(
            [str(model), str(csv_p), "-o", out_p]
        ) == 0
        got = [
            json.loads(ln)
            for ln in pathlib.Path(out_p).read_text().splitlines()
        ]
        assert [g["value"] for g in got] == [
            pytest.approx(20.0), pytest.approx(30.0)
        ]
